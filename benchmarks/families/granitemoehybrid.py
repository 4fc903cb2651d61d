"""``model_type: granitemoehybrid`` through the program: its own config
converter and model class, and a view of the program's parameter tree under
the names the plain reference uses.

The benchmark's random weights are the program's own draw: the Mamba-2
reference implementation's initialisers (``models/granite_hybrid.py``:
``exp(a_log)`` uniform in [1, 16], ``dt_bias`` the inverse softplus of a step
log-uniform in [0.001, 0.1] floored at 1e-4, ``D`` 1, a depthwise Conv1d's
uniform draw), which the published config leaves unstated and the
configuration file's ``assumed`` names, **but for the embedding, which is
drawn ``1 / embedding_multiplier`` as large** (``_QuietEmbedding``; the
configuration file's ``assumed`` and ``serve.logit_margin_why`` say why and
give the readings). The embedding is also the head (``tie_word_embeddings``)
and enters the residual stream times 12: at the program's draw (rows of unit
norm) a token's own row is an eighth of the final state and scores 8 standard
deviations over every other logit, so the model echoes its input, every
top-1 to top-2 gap of the reference is about 0.5, and no fault short of that
moves a token the check compares. Drawn a twelfth as large, ``h_0 = 12
E[token]`` has the unit norm a row has elsewhere and the echo is under one
standard deviation. No shape, byte, operation count or time moves with the
embedding's values. The published ``max_position_embeddings`` (131,072) stays
in the file; ``serve.max_context`` bounds the positions the engine admits.

**And every head is drawn at the LONG end of those initialisers' ranges**
(``_LongMemory``): ``exp(a_log) = 1``, the bottom of [1, 16], and a step of
0.001, the bottom of [0.001, 0.1], so a head forgets over some 600 tokens
(the step a token is ``softplus(dt + dt_bias)``, 0.0004 to 0.003 about
0.0017) where the initialisers' median head forgets over 25. That is what a
trained model's long heads do and what the float32 state is for: with the
initialisers' own draw a state kept in bfloat16, a state dropped at a chunk
boundary 452 tokens back and a slot not zeroed all read inside the sound
runs (``serve.logit_margin_why`` has the readings), so the check could not
refuse them. No shape, byte, operation count or time moves with these values
either."""


class _QuietEmbedding:
    """The model as the harness uses it (``init`` alone), its embedding
    divided by ``embedding_multiplier``."""

    def __init__(self, model):
        self.model = model

    def init(self, key, example):
        variables = self.model.init(key, example)
        params = dict(variables["params"])
        params["embed"] = {"embedding": params["embed"]["embedding"]
                           / self.model.cfg.embedding_multiplier}
        return {**variables, "params": params}


#: a head's ``exp(a_log)`` and its step before the token's own part: the long
#: end of ``models/granite_hybrid.py``'s ``A_RANGE`` and ``DT_MIN``
LONG_A, LONG_STEP = 1.0, 1e-3


class _LongMemory:
    """The model as the harness uses it (``init`` alone), every Mamba-2
    head's ``a_log`` and ``dt_bias`` at the long end of their initialisers'
    ranges."""

    def __init__(self, model):
        self.model = model

    def init(self, key, example):
        import numpy as np
        variables = self.model.init(key, example)
        params = dict(variables["params"])
        for name, layer in params.items():
            if "mamba" in layer:
                mp = layer["mamba"]
                params[name] = {**layer, "mamba": {
                    **mp,
                    "a_log": mp["a_log"] * 0 + np.log(LONG_A),
                    # the inverse softplus, as ``_dt_bias_init`` takes it
                    "dt_bias": mp["dt_bias"] * 0 + LONG_STEP
                    + np.log(-np.expm1(-LONG_STEP))}}
        return {**variables, "params": params}


def build(hf: dict, role: str, options: dict):
    from deepspeed_tpu.models.granite_hybrid import (
        GraniteHybridForCausalLM, granite_hybrid_config_from_hf)
    if role != "serve":
        raise ValueError("granitemoehybrid is benchmarked on the serving "
                         f"path only, not {role!r}")
    cfg = granite_hybrid_config_from_hf(
        hf, max_seq_len=options.get("max_context"))
    return cfg, _LongMemory(
        _QuietEmbedding(GraniteHybridForCausalLM(cfg)))


def compute_dtype(cfg):
    return cfg.dtype


def reference_weights(params) -> dict:
    layers = []
    i = 0
    while f"layer_{i}" in params:
        lp = params[f"layer_{i}"]
        layer = {"mixer_norm": lp["mixer_norm"]["scale"],
                 "mlp_norm": lp["mlp_norm"]["scale"],
                 "gate": lp["mlp"]["w_gate"]["kernel"],
                 "up": lp["mlp"]["w_up"]["kernel"],
                 "down": lp["mlp"]["w_down"]["kernel"]}
        if "mamba" in lp:
            mp = lp["mamba"]
            layer.update(in_proj=mp["in_proj"], conv_w=mp["conv_kernel"],
                         conv_b=mp["conv_bias"], dt_bias=mp["dt_bias"],
                         a_log=mp["a_log"], d=mp["d"], gate_norm=mp["norm"],
                         out_proj=mp["out_proj"])
        else:
            ap = lp["attn"]
            layer.update(q=ap["wq"]["kernel"], k=ap["wk"]["kernel"],
                         v=ap["wv"]["kernel"], o=ap["wo"]["kernel"])
        layers.append(layer)
        i += 1
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "final_norm": params["final_norm"]["scale"]}
