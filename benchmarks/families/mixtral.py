"""``model_type: mixtral`` through the program: its own config converter and
model class, and a view of the program's parameter tree under the names the
plain reference uses."""

import dataclasses


def build(hf: dict, role: str, options: dict):
    from deepspeed_tpu.models.mixtral import (MixtralForCausalLM,
                                              mixtral_config_from_hf)
    cfg = mixtral_config_from_hf(hf)
    if role == "train":
        cfg = dataclasses.replace(cfg, base=dataclasses.replace(
            cfg.base, attention_backend=options["attention_backend"],
            remat=bool(options["remat"])))
    return cfg, MixtralForCausalLM(cfg)


def compute_dtype(cfg):
    return cfg.base.dtype


def reference_weights(params) -> dict:
    layers = []
    i = 0
    while f"layer_{i}" in params:
        lp = params[f"layer_{i}"]
        ex = lp["moe"]["experts"]
        layers.append({
            "attn_norm": lp["attn_norm"]["scale"],
            "q": lp["attn"]["wq"]["kernel"], "k": lp["attn"]["wk"]["kernel"],
            "v": lp["attn"]["wv"]["kernel"], "o": lp["attn"]["wo"]["kernel"],
            "mlp_norm": lp["mlp_norm"]["scale"],
            "router": lp["moe"]["gate"]["wg"]["kernel"],
            "experts": {"gate": ex["w_gate"], "up": ex["w_up"],
                        "down": ex["w_down"]}})
        i += 1
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "final_norm": params["final_norm"]["scale"],
            "head": params["lm_head"]["kernel"]}
