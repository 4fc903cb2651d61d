"""``model_type: qwen2_moe`` through the program: its own config converter and
model class, and a view of the program's parameter tree under the names the
plain reference uses. Test data: ``benchmark_rehearsal.py`` drops this file
into ``families/`` of a temporary copy, as a later PR would add it; the
benchmark has no cell of this family, so ``benchmarks/families/`` has no copy.
"""

import dataclasses


def build(hf: dict, role: str, options: dict):
    from deepspeed_tpu.models.qwen2_moe import (Qwen2MoEForCausalLM,
                                                qwen2_moe_config_from_hf)
    cfg = qwen2_moe_config_from_hf(hf)
    if role == "train":
        cfg = dataclasses.replace(cfg, base=dataclasses.replace(
            cfg.base, attention_backend=options["attention_backend"],
            remat=bool(options["remat"])))
    return cfg, Qwen2MoEForCausalLM(cfg)


def compute_dtype(cfg):
    return cfg.base.dtype


def reference_weights(params) -> dict:
    layers = []
    i = 0
    while f"layer_{i}" in params:
        lp = params[f"layer_{i}"]
        attn, ex, se = lp["attn"], lp["moe"]["experts"], lp["shared_expert"]
        layers.append({
            "attn_norm": lp["attn_norm"]["scale"],
            "q": attn["wq"]["kernel"], "k": attn["wk"]["kernel"],
            "v": attn["wv"]["kernel"], "o": attn["wo"]["kernel"],
            "q_bias": attn["wq"]["bias"], "k_bias": attn["wk"]["bias"],
            "v_bias": attn["wv"]["bias"],
            "mlp_norm": lp["mlp_norm"]["scale"],
            "router": lp["moe"]["gate"]["wg"]["kernel"],
            "experts": {"gate": ex["w_gate"], "up": ex["w_up"],
                        "down": ex["w_down"]},
            "shared": {"gate": se["w_gate"]["kernel"],
                       "up": se["w_up"]["kernel"],
                       "down": se["w_down"]["kernel"],
                       "sigmoid_gate": se["gate"]["kernel"]}})
        i += 1
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "final_norm": params["final_norm"]["scale"],
            "head": params["lm_head"]["kernel"]}
