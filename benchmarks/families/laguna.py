"""``model_type: laguna`` through the program: its own config converter and
model class, and a view of the program's parameter tree under the names the
plain reference uses.

``num_experts`` in a configuration file is what this chip holds of the
router's ``published.num_experts`` (a share cut, ``reduced``), from
``first_expert_held`` on: the router is built at its published width. The
published ``max_position_embeddings`` (1,048,576) stays in the file;
``serve.max_context`` bounds the positions the engine admits and its rope
tables cover (every step program embeds them), to what the cell's traffic can
reach.

The benchmark's random weights are the program's own draw with every router's
kernel doubled (``ROUTER_SPREAD``; the configuration file's ``assumed`` and
``serve.logit_margin_why`` say why and give the readings). The program's
initialiser draws a router like any other matrix."""

#: a random router's logits here have this standard deviation (the program
#: draws them at 1). Every reading behind ``serve.logit_margin_tol`` was taken
#: at 2; which experts are chosen, and so every row count and time, is the
#: same at any spread
ROUTER_SPREAD = 2.0


class _WiderRouters:
    """The model as the harness uses it (``init`` alone), its routers'
    kernels times ``ROUTER_SPREAD``."""

    def __init__(self, model):
        self.model = model

    def init(self, key, example):
        variables = self.model.init(key, example)
        params = {name: (layer if "moe" not in layer else {
            **layer, "moe": {**layer["moe"], "gate": {
                "kernel": layer["moe"]["gate"]["kernel"] * ROUTER_SPREAD}}})
            for name, layer in variables["params"].items()}
        return {**variables, "params": params}


def build(hf: dict, role: str, options: dict):
    from deepspeed_tpu.models.laguna import (LagunaForCausalLM,
                                             laguna_config_from_hf)
    if role != "serve":
        raise ValueError("laguna is benchmarked on the serving path only, "
                         f"not {role!r}")
    cfg = laguna_config_from_hf(
        hf, max_seq_len=options.get("max_context"),
        router_experts=hf.get("published", {}).get("num_experts"),
        first_expert=hf.get("first_expert_held", 0))
    return cfg, _WiderRouters(LagunaForCausalLM(cfg))


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
                 "q": ap["wq"]["kernel"], "k": ap["wk"]["kernel"],
                 "v": ap["wv"]["kernel"], "g": ap["wg"]["kernel"],
                 "o": ap["wo"]["kernel"],
                 "mlp_norm": lp["mlp_norm"]["scale"]}
        if "moe" in lp:
            moe = lp["moe"]
            ex = moe["experts"]
            layer.update(router=moe["gate"]["kernel"],
                         experts={"gate": ex["w_gate"], "up": ex["w_up"],
                                  "down": ex["w_down"]},
                         shared=swiglu(moe["shared"]))
        else:
            layer.update(swiglu(lp["mlp"]))
        layers.append(layer)
        i += 1
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "final_norm": params["final_norm"]["scale"],
            "head": params["lm_head"]["kernel"]}
