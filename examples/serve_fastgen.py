"""Continuous-batching serving (FastGen analog).

DeepSpeedExamples/MII analog: build an InferenceEngineV2 over any registered
architecture, admit a ragged wave of requests through put/can_schedule,
step the engine, and flush completions — with device-side sampling.

`python examples/serve_fastgen.py --arch bloom` (llama | falcon | opt |
mixtral | bloom | gpt_neox | gpt2).
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

ARCHS = {
    "llama": ("deepspeed_tpu.models.llama", "TINY_LLAMA", "LlamaForCausalLM"),
    "falcon": ("deepspeed_tpu.models.falcon", "TINY_FALCON", "FalconForCausalLM"),
    "opt": ("deepspeed_tpu.models.opt", "TINY_OPT", "OPTForCausalLM"),
    "mixtral": ("deepspeed_tpu.models.mixtral", "TINY_MIXTRAL", "MixtralForCausalLM"),
    "bloom": ("deepspeed_tpu.models.bloom", "TINY_BLOOM", "BloomForCausalLM"),
    "gpt_neox": ("deepspeed_tpu.models.gpt_neox", "TINY_NEOX", "GPTNeoXForCausalLM"),
    "gpt2": ("deepspeed_tpu.models.gpt2", "TINY_GPT2", "GPT2ForCausalLM"),
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama", choices=sorted(ARCHS))
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--max_new_tokens", type=int, default=8)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--speculative_k", type=int, default=0,
                   help="also demo draft-free speculative decoding (greedy)")
    args = p.parse_args()

    import importlib

    import jax
    import numpy as np

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, V2EngineConfig)
    from deepspeed_tpu.inference.v2.sampling import SamplingConfig

    mod_name, cfg_name, cls_name = ARCHS[args.arch]
    mod = importlib.import_module(mod_name)
    cfg, model = getattr(mod, cfg_name), getattr(mod, cls_name)(getattr(mod, cfg_name))
    rng = np.random.default_rng(0)
    init_batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, size=(1, 8)).astype(np.int32)}
    params = model.init(jax.random.PRNGKey(0), init_batch)["params"]

    engine = InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=256,
        sampling=SamplingConfig(temperature=args.temperature, top_k=40,
                                seed=0)))

    prompts = {uid: list(rng.integers(0, cfg.vocab_size,
                                      size=rng.integers(4, 12)))
               for uid in range(args.requests)}
    pending = dict(prompts)
    in_flight = set()
    done = {}
    while pending or in_flight:
        # grow the admitted wave while the BATCH still fits (put() re-checks
        # the combined batch, so admission must be checked combined too)
        admit = []
        for u in list(pending):
            if engine.can_schedule(admit + [u],
                                   [len(pending[c]) for c in admit] +
                                   [len(pending[u])]):
                admit.append(u)
        if admit:
            engine.put(admit, [pending.pop(u) for u in admit])
            in_flight.update(admit)
        engine.step()
        for uid in list(in_flight):
            if len(engine.state.get(uid).generated) >= args.max_new_tokens:
                # put()/step() may overshoot by a token; honor the budget
                done[uid] = engine.flush(uid)[:args.max_new_tokens]
                in_flight.discard(uid)
    for uid in sorted(done):
        print(f"request {uid}: prompt {len(prompts[uid])} tokens -> "
              f"{done[uid]}")
    assert len(done) == args.requests
    print(f"{args.arch}: served {len(done)} requests")

    if args.speculative_k > 0:
        # serial speculative generation on the same weights (greedy-exact,
        # 1..k+1 tokens per verify step; prompt-lookup hits on repetitive
        # prompts)
        spec = InferenceEngineV2(params, cfg, V2EngineConfig(
            kv_block_size=16, kv_num_blocks=256,
            speculative_k=args.speculative_k))
        base = list(rng.integers(0, cfg.vocab_size, size=5))
        out = spec.generate(base * 4, max_new_tokens=args.max_new_tokens * 2)
        st = spec.speculative_stats()
        if st["steps"]:
            print(f"speculative k={args.speculative_k}: {len(out)} tokens, "
                  f"{st['tokens_per_step']:.2f} tokens/step on verify steps "
                  f"(accepted {st['accepted']}/{st['proposed']})")
        else:
            # a randomly-initialized model never re-emits its context's
            # n-grams, so lookup proposals don't fire — generation stays
            # exact via the 1-token fallback; real LMs repeat constantly
            print(f"speculative k={args.speculative_k}: {len(out)} tokens, "
                  "no lookup hits on this random tiny model (exact greedy "
                  "fallback; proposals engage on repetitive text)")


if __name__ == "__main__":
    main()
