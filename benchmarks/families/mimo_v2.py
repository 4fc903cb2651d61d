"""``model_type: mimo_v2`` through the program: its own config converter and
model class, and a view of the program's parameter tree under the names the
plain reference uses.

``n_routed_experts`` in a configuration file is what this chip holds of the
router's ``published.n_routed_experts`` (a share cut, ``reduced``), from
``first_expert_held`` on: the router is built at its published width. The
published ``max_position_embeddings`` (1,048,576) stays in the file;
``serve.max_context`` bounds the positions the engine admits and its rope
tables cover (every step program embeds them), to what the cell's traffic can
reach.

The benchmark's random weights are the program's own draw with every windowed
layer's sinks drawn N(``SINK_MEAN``, ``SINK_SPREAD``): the program's
initialiser leaves a sink at 0. With random weights a head's scores are about
N(0, 1), so the up to 128 keys of a window sum to about 128 x e^0.5 = 211 in
the softmax's denominator: a sink of N(0, 1) (what ISSUE 41 names) adds 1.6
to that, under a hundredth of the head's mass and inside bfloat16's noise,
and a check could not tell it from no sink at all; a sink of N(4, 1) adds
about 90, a third of the mass, which is what a sink is for (it takes the
attention a head has no key to give to). The configuration file's ``assumed``
says so, and ``serve.logit_margin_why`` gives the control's readings."""

#: mean and standard deviation of the sinks the benchmark draws
SINK_MEAN = 4.0
SINK_SPREAD = 1.0


class _DrawnSinks:
    """The model as the harness uses it (``init`` alone), its layers' sinks
    drawn from the key."""

    def __init__(self, model):
        self.model = model

    def init(self, key, example):
        import jax
        variables = self.model.init(key, example)
        params = dict(variables["params"])
        for i, name in enumerate(sorted(params)):
            attn = params[name].get("attn") if "layer_" in name else None
            if attn is not None and "sink" in attn:
                sink = SINK_MEAN + SINK_SPREAD * jax.random.normal(
                    jax.random.fold_in(key, 7919 + i), attn["sink"].shape,
                    attn["sink"].dtype)
                params[name] = {**params[name], "attn": {**attn,
                                                         "sink": sink}}
        return {**variables, "params": params}


def build(hf: dict, role: str, options: dict):
    from deepspeed_tpu.models.mimo_v2 import (MiMoV2ForCausalLM,
                                              mimo_v2_config_from_hf)
    if role != "serve":
        raise ValueError("mimo_v2 is benchmarked on the serving path only, "
                         f"not {role!r}")
    cfg = mimo_v2_config_from_hf(
        hf, max_seq_len=options.get("max_context"),
        router_experts=hf.get("published", {}).get("n_routed_experts"),
        first_expert=hf.get("first_expert_held", 0))
    return cfg, _DrawnSinks(MiMoV2ForCausalLM(cfg))


def compute_dtype(cfg):
    return cfg.dtype


def reference_weights(params) -> dict:
    layers = []
    i = 0
    while f"layer_{i}" in params:
        lp = params[f"layer_{i}"]
        ap = lp["attn"]
        layer = {"attn_norm": lp["attn_norm"]["scale"],
                 "q": ap["wq"]["kernel"], "k": ap["wk"]["kernel"],
                 "v": ap["wv"]["kernel"], "o": ap["wo"]["kernel"],
                 "mlp_norm": lp["mlp_norm"]["scale"]}
        if "sink" in ap:
            layer["sink"] = ap["sink"]
        if "moe" in lp:
            moe = lp["moe"]
            ex = moe["experts"]
            layer.update(router=moe["gate"]["kernel"],
                         router_bias=moe["gate"]["bias"],
                         experts={"gate": ex["w_gate"], "up": ex["w_up"],
                                  "down": ex["w_down"]})
        else:
            mlp = lp["mlp"]
            layer.update(gate=mlp["w_gate"]["kernel"],
                         up=mlp["w_up"]["kernel"],
                         down=mlp["w_down"]["kernel"])
        layers.append(layer)
        i += 1
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "final_norm": params["final_norm"]["scale"],
            "head": params["lm_head"]["kernel"]}
