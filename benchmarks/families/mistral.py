"""``model_type: mistral`` through the program: its own config converter and
model class, and a view of the program's parameter tree under the names the
plain reference uses."""

import dataclasses


def build(hf: dict, role: str, options: dict):
    """(program config, flax model) for a published ``config.json`` dict.
    ``role`` is "serve" or "train"; ``options`` is the configuration file's
    group of that name."""
    from deepspeed_tpu.models.families import config_from_hf
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    cfg = config_from_hf(hf)
    if role == "train":
        cfg = dataclasses.replace(
            cfg, attention_backend=options["attention_backend"],
            remat=bool(options["remat"]))
    return cfg, LlamaForCausalLM(cfg)


def compute_dtype(cfg):
    return cfg.dtype


def reference_weights(params) -> dict:
    m = params["model"]
    layers = []
    i = 0
    while f"layer_{i}" in m:
        lp = m[f"layer_{i}"]
        layers.append({
            "attn_norm": lp["attn_norm"]["scale"],
            "q": lp["attn"]["wq"]["kernel"], "k": lp["attn"]["wk"]["kernel"],
            "v": lp["attn"]["wv"]["kernel"], "o": lp["attn"]["wo"]["kernel"],
            "mlp_norm": lp["mlp_norm"]["scale"],
            "gate": lp["mlp"]["w_gate"]["kernel"],
            "up": lp["mlp"]["w_up"]["kernel"],
            "down": lp["mlp"]["w_down"]["kernel"]})
        i += 1
    return {"embed": m["embed"]["embedding"], "layers": layers,
            "final_norm": m["final_norm"]["scale"],
            "head": m["lm_head"]["kernel"]}
