"""``model_type: lfm2_moe`` through the program: its own config converter and
model class, and a view of the program's parameter tree under the names the
plain reference uses.

Every expert of a layer is held (the cut is depth alone), so a router near-tie
that bfloat16 breaks the other way than float32 swaps two experts that are
both here, and the redraw Nemotron's held share forced (``_QuietRouted``) is
not needed. The published ``max_position_embeddings`` (128,000) stays in the
file; ``serve.max_context`` bounds the positions the engine admits and the
rope tables a step program embeds.

The benchmark's random weights are the program's own draw (every matrix
lecun-normal, norms 1, the taps a depthwise Conv1d's uniform draw, the experts
as sparse upcycling leaves them, ``models/joyai_llm_flash.py`` ``_upcycled``),
but for three redraws, all named in the configuration file's ``assumed``: **a
router's ``expert_bias`` is DEALT, not drawn** (``_DealtBias``, Nemotron's,
for Nemotron's reason): every expert layer gets the same ladder of 64 values,
N(0, 0.1) at evenly spaced quantiles, in an order of the seed's, as the
traffic generator deals one multiset of lengths to every seed. The choice is
the 4 largest of a sigmoid score plus the bias, so the bias decides how often
an expert is chosen at all, and with a DRAWN bias the seed decides how many
experts a tick's rows touch and so how many bytes it reads (what spread
PR 48's and PR 49's first cells). The values are not zero, so a choice that
ignores them picks other experts and is refused (``controls/lfm2_moe.py``
``bias_ignored``). **And the scales of the norms a head of q and k are DRAWN,
not 1** (``_DrawnHeadNorms``): the program's own initialiser leaves them 1,
and q and k of a random model have an RMS of about 1 a head as they are, so a
program that skipped the norm, or its scale, computed nearly the same scores
(``no_qk_norm`` read 0.25 beside sound runs of 0.11-0.15, my chip run, PR 52).
Each of a scale's 64 values is drawn log-uniform in [1/2, 2], as a trained
model's norms lie about 1 and not on it. **And a layer's experts are 1/8 of a
draw of their own apart, not 1/16** (``_SpreadExperts``): at 1/16 a router
that chose OTHER experts moved a token by so little that ``bias_ignored`` and
``softmax_router`` read 1.1 to 1.4 times the check's limit (0.31-0.49 over
0.28, four seeds); at 1/8 they read 0.92-1.21 where sound runs read
0.25-0.38 (my chip runs, PR 52; ``serve.logit_margin_why`` has every
reading). No shape, byte count or operation count moves with any of the
three."""

from benchmarks.families.nemotron_h import _DealtBias

#: a head norm's scale is drawn log-uniform within this factor of 1
HEAD_NORM_SPREAD = 2.0
#: the part of a draw of its own by which an expert differs from its layer's
#: shared draw (the program's initialiser leaves 1/16)
EXPERT_SPREAD = 1 / 8


class _DrawnHeadNorms:
    """The model as the harness uses it (``init`` alone), every attention
    layer's ``q_norm`` and ``k_norm`` scales drawn log-uniform in
    [1 / ``HEAD_NORM_SPREAD``, ``HEAD_NORM_SPREAD``]."""

    def __init__(self, model):
        self.model = model

    def init(self, key, example):
        import math

        import jax
        import jax.numpy as jnp
        variables = self.model.init(key, example)
        params = dict(variables["params"])
        width = math.log(HEAD_NORM_SPREAD)
        for i, (name, layer) in enumerate(sorted(params.items())):
            if "attn" not in layer:
                continue
            attn = dict(layer["attn"])
            keys = jax.random.split(jax.random.fold_in(key, 1000 + i))
            for k, which in zip(keys, ("q_norm", "k_norm")):
                attn[which] = jnp.exp(jax.random.uniform(
                    k, attn[which].shape, attn[which].dtype, -width, width))
            params[name] = {**layer, "attn": attn}
        return {**variables, "params": params}


class _SpreadExperts:
    """The model as the harness uses it (``init`` alone), every expert
    layer's experts ``EXPERT_SPREAD`` of a draw of their own apart where the
    program's initialiser leaves them 1/16: each stack [E, in, out] keeps its
    mean over E (what all share) and its experts' parts beside the mean are
    scaled."""

    def __init__(self, model):
        self.model = model

    def init(self, key, example):
        from deepspeed_tpu.models.joyai_llm_flash import EXPERT_SPREAD as own
        variables = self.model.init(key, example)
        params = dict(variables["params"])
        for name, layer in params.items():
            if "moe" not in layer:
                continue
            experts = {}
            for which, w in layer["moe"]["experts"].items():
                shared = w.mean(axis=0, keepdims=True)
                experts[which] = shared + (EXPERT_SPREAD / own) * (w - shared)
            params[name] = {**layer, "moe": {**layer["moe"],
                                             "experts": experts}}
        return {**variables, "params": params}


def build(hf: dict, role: str, options: dict):
    from deepspeed_tpu.models.lfm2_moe import (Lfm2MoeForCausalLM,
                                               lfm2_moe_config_from_hf)
    if role != "serve":
        raise ValueError("lfm2_moe is benchmarked on the serving path only, "
                         f"not {role!r}")
    cfg = lfm2_moe_config_from_hf(hf, max_seq_len=options.get("max_context"))
    return cfg, _DealtBias(_SpreadExperts(_DrawnHeadNorms(
        Lfm2MoeForCausalLM(cfg))), cfg.num_experts)


def compute_dtype(cfg):
    return cfg.dtype


def reference_weights(params) -> dict:
    layers = []
    i = 0
    while f"layer_{i}" in params:
        lp = params[f"layer_{i}"]
        layer = {"operator_norm": lp["mixer_norm"]["scale"],
                 "ffn_norm": lp["mlp_norm"]["scale"]}
        if "conv" in lp:
            cp = lp["conv"]
            layer.update(in_proj=cp["in_proj"], taps=cp["conv_kernel"],
                         out_proj=cp["out_proj"])
        else:
            ap = lp["attn"]
            layer.update(q=ap["wq"]["kernel"], k=ap["wk"]["kernel"],
                         v=ap["wv"]["kernel"], o=ap["wo"]["kernel"],
                         q_norm=ap["q_norm"], k_norm=ap["k_norm"])
        if "mlp" in lp:
            layer.update(w1=lp["mlp"]["w_gate"]["kernel"],
                         w3=lp["mlp"]["w_up"]["kernel"],
                         w2=lp["mlp"]["w_down"]["kernel"])
        else:
            moe = lp["moe"]
            layer.update(router=moe["gate"]["kernel"],
                         expert_bias=moe["gate"]["bias"],
                         experts={"w1": moe["experts"]["w_gate"],
                                  "w3": moe["experts"]["w_up"],
                                  "w2": moe["experts"]["w_down"]})
        layers.append(layer)
        i += 1
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "final_norm": params["final_norm"]["scale"]}
