"""``model_type: xing4_0`` through the program: its own config converter and
model class, and a view of the program's parameter tree under the names the
plain reference uses.

The published ``max_position_embeddings`` (262,144) stays in the
configuration's file; ``serve.max_context`` bounds the positions the engine
admits and its rope tables cover (every step program embeds them), to what
the cell's traffic can reach.

The benchmark's random weights are the program's own draw except for the
mixing parameters of the hyper-connections (``trained_mixing``). The
program's initialiser is the papers': ``H_res`` the identity, ``H_pre``
``1 / n``, ``H_post`` 1 and ``alpha`` 0.01, at which the mechanism is a plain
residual and no check could tell it from one. Here they are drawn as a
trained model's would lie: ``alpha`` 1 (``phi`` is drawn at unit variance of
``r phi``, so the token's part of every coefficient's logit has a standard
deviation of 1) and every bias N(0, 1): ``H_res`` far from the identity and
different from token to token, Sinkhorn-Knopp needing its iterations (20
leave some tokens' rows 1e-3 off 1). The configuration file's ``assumed`` and
``serve.logit_margin_why`` give the readings."""


def trained_mixing(params, key):
    """``params`` with every sublayer's ``alpha`` 1 and its biases drawn
    N(0, 1) (module docstring): jit-traceable, the other leaves untouched."""
    import jax
    import jax.numpy as jnp
    out = dict(params)
    for name in params:
        if not name.startswith("layer_"):
            continue
        layer = dict(params[name])
        for which, sub in enumerate(("hc_attn", "hc_mlp")):
            mixing = layer[sub]
            draw = jax.random.normal(
                jax.random.fold_in(key, 2 * int(name.split("_")[1]) + which),
                mixing["bias"].shape, jnp.float32)
            layer[sub] = {"phi": mixing["phi"],
                          "alpha": jnp.ones_like(mixing["alpha"]),
                          "bias": draw.astype(mixing["bias"].dtype)}
        out[name] = layer
    return out


class _TrainedMixing:
    """The model as the harness uses it (``init`` alone), its mixing
    parameters drawn by ``trained_mixing``."""

    def __init__(self, model):
        self.model = model

    def init(self, key, example):
        import jax
        variables = self.model.init(key, example)
        return {**variables, "params": trained_mixing(
            variables["params"], jax.random.fold_in(key, 0x6d4843))}


def build(hf: dict, role: str, options: dict):
    from deepspeed_tpu.models.xing4 import (Xing4ForCausalLM,
                                            xing4_config_from_hf)
    if role != "serve":
        raise ValueError("xing4_0 is benchmarked on the serving path only, "
                         f"not {role!r}")
    cfg = xing4_config_from_hf(hf, max_seq_len=options.get("max_context"))
    return cfg, _TrainedMixing(Xing4ForCausalLM(cfg))


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
                 "mlp_norm": lp["mlp_norm"]["scale"],
                 "hc_attn": dict(lp["hc_attn"]), "hc_mlp": dict(lp["hc_mlp"])}
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
