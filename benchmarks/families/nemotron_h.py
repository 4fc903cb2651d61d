"""``model_type: nemotron_h`` through the program: its own config converter
and model class, and a view of the program's parameter tree under the names
the plain reference uses.

``n_routed_experts`` in a configuration file is what this chip holds of the
router's ``published.n_routed_experts`` (a share cut, ``reduced``), from
``first_expert_held`` on: the router is built at its published width. The
published ``max_position_embeddings`` (262,144) stays in the file;
``serve.max_context`` bounds the positions the engine admits (no table hangs
on it: the model has no positional embedding).

The benchmark's random weights are the program's own draw (the Mamba-2
reference implementation's initialisers, ``models/granite_hybrid.py``; the
experts as sparse upcycling leaves them, ``models/joyai_llm_flash.py``
``_upcycled``; a router's ``e_score_correction_bias`` of deviation 0.1, so NOT
zero: a choice that ignores it picks other experts), but for three redraws,
all named in the configuration file's ``assumed`` with the readings in
``serve.logit_margin_why``. **Every Mamba-2 head is drawn at the LONG end of
its initialisers' ranges** (``_LongMemory``, granite's, for granite's reason:
a head then forgets over some 600 tokens where the initialisers' median head
forgets over 25, which is what a trained model's long heads do and what the
float32 state is for). **Every routed expert's second matrix is drawn 1/16 as
large** (``_QuietRouted``): this chip holds half of the router's experts, and
where the seventh of 128 scores lies within bfloat16's noise of the sixth
(about a tenth of the tokens of an expert layer) the program and the float32
reference choose different experts; in a whole layer two nearly alike experts
swap and nothing shows, here one of the pair is often the absent chip's and a
whole expert's part comes or goes. At the program's own draw a sound run read
1.3-1.9 where a state kept in bfloat16 has to be seen at 0.2. No shape, byte,
operation count or time moves with either redraw (which experts are chosen is
the same at any scale). **A router's ``e_score_correction_bias`` is DEALT,
not drawn** (``_DealtBias``): every chip's share of the router gets the same
ladder of values, N(0, 0.1) at evenly spaced quantiles, in an order of the
seed's, as the traffic generator deals one multiset of lengths to every seed
(``benchmarks/harness/traffic.py``). The choice is the 6 largest of a sigmoid
score (about 0.2 from one token to the next) plus a bias of deviation 0.1, so
the bias decides how often an expert is chosen at all: of 128 decode rows'
768 choices, 45 of this chip's 64 experts get a row in a layer, and with the
program's own draw WHICH seed decided how many (199-239 of 5 x 64 over 24
seeds by the router's arithmetic alone, so 6% in the bytes a decode tick
reads of its experts, which are two fifths of its time): six seeds of the
cell spread 1.7-2.3%, which the deal of the requests does not explain (a
replay of the scheduler gives 0.5%). Dealt, every seed's router is as uneven
as every other's and both chips of the pair get the same share of the rows;
the values, and so what a choice without them loses, are the draw's own."""


from benchmarks.families.granitemoehybrid import _LongMemory

#: what a routed expert's second matrix is drawn at, of the program's own draw
ROUTED_DOWN_SCALE = 1 / 16


class _QuietRouted:
    """The model as the harness uses it (``init`` alone), every routed
    expert's ``w_down`` times ``ROUTED_DOWN_SCALE``."""

    def __init__(self, model):
        self.model = model

    def init(self, key, example):
        variables = self.model.init(key, example)
        params = dict(variables["params"])
        for name, layer in params.items():
            if "moe" in layer:
                moe = layer["moe"]
                params[name] = {**layer, "moe": {**moe, "experts": {
                    **moe["experts"], "w_down":
                    moe["experts"]["w_down"] * ROUTED_DOWN_SCALE}}}
        return {**variables, "params": params}


#: the deviation of ``models/joyai_llm_flash.py`` ``_Router``'s bias draw
BIAS_DEVIATION = 0.1


class _DealtBias:
    """The model as the harness uses it (``init`` alone), every router's
    ``e_score_correction_bias`` one ladder of N(0, ``BIAS_DEVIATION``)
    quantiles a chip's share of the router (``held`` experts), each share's
    in an order of the key's."""

    def __init__(self, model, held: int):
        self.model, self.held = model, held

    def init(self, key, example):
        import statistics

        import jax
        import jax.numpy as jnp
        variables = self.model.init(key, example)
        params = dict(variables["params"])
        for i, (name, layer) in enumerate(sorted(params.items())):
            if "moe" not in layer:
                continue
            gate = layer["moe"]["gate"]
            ladder = jnp.asarray(
                [statistics.NormalDist(0.0, BIAS_DEVIATION).inv_cdf(
                    (j + 0.5) / self.held) for j in range(self.held)],
                gate["bias"].dtype)
            keys = jax.random.split(jax.random.fold_in(key, i),
                                    gate["bias"].shape[0] // self.held)
            bias = jnp.concatenate(
                [jax.random.permutation(k, ladder) for k in keys])
            params[name] = {**layer, "moe": {
                **layer["moe"], "gate": {**gate, "bias": bias}}}
        return {**variables, "params": params}


def build(hf: dict, role: str, options: dict):
    from deepspeed_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                                 nemotron_h_config_from_hf)
    if role != "serve":
        raise ValueError("nemotron_h is benchmarked on the serving path "
                         f"only, not {role!r}")
    cfg = nemotron_h_config_from_hf(
        hf, max_seq_len=options.get("max_context"),
        router_experts=hf.get("published", {}).get("n_routed_experts"),
        first_expert=hf.get("first_expert_held", 0))
    return cfg, _DealtBias(
        _QuietRouted(_LongMemory(NemotronHForCausalLM(cfg))), cfg.held)


def compute_dtype(cfg):
    return cfg.dtype


def reference_weights(params) -> dict:
    layers = []
    i = 0
    while f"layer_{i}" in params:
        lp = params[f"layer_{i}"]
        layer = {"norm": lp["norm"]["scale"]}
        if "mamba" in lp:
            mp = lp["mamba"]
            layer.update(in_proj=mp["in_proj"], conv_w=mp["conv_kernel"],
                         conv_b=mp["conv_bias"], dt_bias=mp["dt_bias"],
                         a_log=mp["a_log"], d=mp["d"], gate_norm=mp["norm"],
                         out_proj=mp["out_proj"])
        elif "attn" in lp:
            ap = lp["attn"]
            layer.update(q=ap["wq"]["kernel"], k=ap["wk"]["kernel"],
                         v=ap["wv"]["kernel"], o=ap["wo"]["kernel"])
        else:
            moe = lp["moe"]
            layer.update(router=moe["gate"]["kernel"],
                         router_bias=moe["gate"]["bias"],
                         experts={"in": moe["experts"]["w_in"],
                                  "down": moe["experts"]["w_down"]})
            if "shared" in moe:
                layer["shared"] = {"up": moe["shared"]["w_up"]["kernel"],
                                   "down": moe["shared"]["w_down"]["kernel"]}
        layers.append(layer)
        i += 1
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "final_norm": params["final_norm"]["scale"],
            "head": params["lm_head"]["kernel"]}
