"""``model_type: joyai_llm_flash`` through the program: its own config
converter and model class, and a view of the program's parameter tree under
the names the plain reference uses.

The published ``max_position_embeddings`` (131,072) stays in the
configuration's file; ``serve.max_context`` bounds the positions the engine
admits and its rope tables cover (every step program embeds them, 512 bytes
a position), to what the cell's traffic can reach."""


def build(hf: dict, role: str, options: dict):
    from deepspeed_tpu.models.joyai_llm_flash import (
        JoyAIFlashForCausalLM, joyai_flash_config_from_hf)
    if role != "serve":
        raise ValueError("joyai_llm_flash is benchmarked on the serving "
                         f"path only, not {role!r}")
    cfg = joyai_flash_config_from_hf(hf,
                                     max_seq_len=options.get("max_context"))
    return cfg, JoyAIFlashForCausalLM(cfg)


def compute_dtype(cfg):
    return cfg.dtype


def reference_weights(params) -> dict:
    def swiglu(p):
        return {"gate": p["w_gate"]["kernel"], "up": p["w_up"]["kernel"],
                "down": p["w_down"]["kernel"]}
    layers = []
    i = 0
    while f"layer_{i}" in params:
        lp = params[f"layer_{i}"]
        ap = lp["attn"]
        layer = {"attn_norm": lp["attn_norm"]["scale"],
                 "q_a": ap["wq_a"]["kernel"], "q_a_norm": ap["q_norm"]["scale"],
                 "q_b": ap["wq_b"]["kernel"], "kv_a": ap["wkv_a"]["kernel"],
                 "kv_a_norm": ap["kv_norm"]["scale"],
                 "kv_b": ap["wkv_b"]["kernel"], "o": ap["wo"]["kernel"],
                 "mlp_norm": lp["mlp_norm"]["scale"]}
        if "moe" in lp:
            moe = lp["moe"]
            ex = moe["experts"]
            layer.update(router=moe["gate"]["kernel"],
                         router_bias=moe["gate"]["bias"],
                         experts={"gate": ex["w_gate"], "up": ex["w_up"],
                                  "down": ex["w_down"]})
            if "shared" in moe:
                layer["shared"] = swiglu(moe["shared"])
        else:
            layer.update(swiglu(lp["mlp"]))
        layers.append(layer)
        i += 1
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "final_norm": params["final_norm"]["scale"],
            "head": params["lm_head"]["kernel"]}
