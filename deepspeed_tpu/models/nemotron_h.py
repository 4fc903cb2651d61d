"""Nemotron-H causal LM (``model_type: nemotron_h``; NVIDIA-Nemotron-3-Nano-
30B-A3B, 52 layers): every layer is ONE mixer behind one RMSNorm, ``h <- h +
Mixer_l(RMSNorm_l(h))``, chosen by the layer's letter in
``hybrid_override_pattern``: a Mamba-2 state-space mixer (``M``), routed
experts (``E``) or softmax attention (``*``). A final RMSNorm, an untied head,
no multiplier anywhere and no positional embedding.

Reference analog: none in ``deepspeed/inference/v2/model_implementations``.
The mixers, ``u = RMSNorm_l(h)``:

- ``M`` (Mamba-2, ``models/granite_hybrid.py``'s mixer with ``mamba_groups``
  groups of ``B`` and ``C``): ``[z ; xBC ; dt] = W_in u``; ``xBC`` through a
  causal depthwise convolution of ``mamba_d_conv`` taps with bias, then silu;
  ``[x ; B ; C] = xBC`` with ``B``, ``C`` ``[groups, d_state]``; head ``j``
  reads group ``j // (heads / groups)``'s; ``step = softplus(dt + dt_bias)``,
  ``S <- exp(step A) S + step x (x) B_g``, ``y = S C_g + D x``; ``y <-
  RMSNorm(y * silu(z))`` over EACH GROUP's ``d_inner / groups`` values (the
  gate before the norm, one learned scale of ``d_inner``); ``W_out``.
- ``E``: ``s = sigmoid(W_r u)`` in float32, the ``num_experts_per_tok``
  largest of ``s + bias``, weights ``s`` of the chosen renormalised times
  ``routed_scaling_factor`` (``moe/grouped_experts.py`` ``sigmoid_route``);
  ``y = sum_k w_k W_down,k relu(W_up,k u)^2 + W_sdown relu(W_sup u)^2``: an
  expert is TWO matrices and no gate (``mlp_hidden_act: relu2``), beside one
  shared expert of its own width. The experts stacked here may be a share of
  the router's (``experts_held`` from ``first_expert`` on): the router keeps
  its width and top-k, and what falls on an absent expert is left out.
- ``*``: ``num_heads`` query heads of ``head_dim`` (together wider than the
  hidden size) over ``num_kv_heads`` KV heads, no bias, no rotary or other
  position, causal ``softmax(q k^T / sqrt(head_dim)) v``, ``W_o``.

What is refused by name (``nemotron_h_config_from_hf``): a pattern letter
other than these three (the family's dense ``-`` MLP layer), biases, an
activation other than ``relu2`` in the experts or ``silu`` in the mixer, a
grouped choice of experts (``n_group`` or ``topk_group`` over 1), tied
embeddings. Serving: ``inference/v2/modules.py`` ``NemotronHPolicy``.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.granite_hybrid import GraniteAttention, GraniteMamba
from deepspeed_tpu.models.joyai_llm_flash import _Router, _upcycled
from deepspeed_tpu.models.llama import LMHead, RMSNorm
from deepspeed_tpu.moe.grouped_experts import grouped_expert_ffn, relu2

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    # a letter a layer: ``M`` Mamba-2, ``E`` routed experts, ``*`` attention
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_groups: int = 8
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    moe_intermediate_size: int = 1856     # one routed expert
    shared_intermediate_size: int = 3712  # the shared expert
    n_routed_experts: int = 128           # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    # the share of the router's experts stacked here: ``experts_held`` of
    # them from ``first_expert`` on (None: all)
    experts_held: Optional[int] = None
    first_expert: int = 0
    max_seq_len: int = 262144
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.pattern) - {MAMBA, EXPERTS, ATTENTION} \
                or self.num_heads % self.num_kv_heads \
                or self.mamba_heads % self.mamba_groups:
            raise ValueError(
                f"pattern {self.pattern!r} has a letter that is none of "
                f"{MAMBA!r}, {EXPERTS!r}, {ATTENTION!r}, or {self.num_heads} "
                f"query heads do not divide over {self.num_kv_heads} KV "
                f"heads, or {self.mamba_heads} Mamba heads over "
                f"{self.mamba_groups} groups")
        if not 0 < self.held <= self.n_routed_experts - self.first_expert:
            raise ValueError(f"experts {self.first_expert} .. "
                             f"{self.first_expert + self.held - 1} are not "
                             f"among the router's {self.n_routed_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def held(self) -> int:
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    # the Mamba-2 mixer's widths, under the names ``granite_hybrid``'s mixer
    # reads them by
    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        """``[x ; B ; C]``: what the convolution runs over."""
        return self.d_inner + 2 * self.mamba_groups * self.mamba_d_state

    @property
    def in_proj_width(self) -> int:
        return self.d_inner + self.conv_channels + self.mamba_heads

    @property
    def norm_groups(self) -> int:
        """The gated norm normalises each group of ``B`` and ``C``'s heads
        alone."""
        return self.mamba_groups

    @property
    def attention_multiplier(self) -> float:
        return self.head_dim ** -0.5


TINY_NEMOTRON_H = NemotronHConfig(
    vocab_size=512, hidden_size=64, pattern="MEM*EM", num_heads=4,
    num_kv_heads=2, head_dim=32, mamba_heads=8, mamba_head_dim=8,
    mamba_d_state=16, mamba_groups=4, mamba_chunk_size=16,
    moe_intermediate_size=24, shared_intermediate_size=48,
    n_routed_experts=16, num_experts_per_tok=3, max_seq_len=512)


# ---------------------------------------------------------------------------
# flax modules (the plain form: no cache). The Mamba-2 mixer and the
# attention without positions are ``granite_hybrid``'s, read by this config's
# widths
# ---------------------------------------------------------------------------
def _dense(features, name, cfg):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, name=name)


class _Relu2MLP(nn.Module):
    """``W_down relu(W_up x)^2``: the shared expert."""
    cfg: NemotronHConfig
    width: int

    @nn.compact
    def __call__(self, x):
        up = _dense(self.width, "w_up", self.cfg)(x)
        return _dense(self.cfg.hidden_size, "w_down", self.cfg)(relu2(up))


class _Relu2Experts(nn.Module):
    """The stacked weights of the routed experts held here, two matrices an
    expert and both ``[E, F, D]`` (a hidden unit's row in, ``w_in``, as a
    checkpoint's ``up_proj.weight`` has it, and its row out, ``w_down``:
    ``moe/grouped_experts.py`` says why), drawn as ``joyai_llm_flash.
    _upcycled`` draws a gated expert's."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, h, weights, ids):
        cfg = self.cfg
        e, d, f = cfg.held, cfg.hidden_size, cfg.moe_intermediate_size

        def rows_in(key, shape, dtype=jnp.float32):
            # fan-in D, fan-out F, as the [E, D, F] draw has them
            e, f, d = shape
            return jnp.swapaxes(_upcycled(key, (e, d, f), dtype), 1, 2)
        experts = {
            "w_in": self.param("w_in", rows_in, (e, f, d), jnp.float32),
            "w_down": self.param("w_down", _upcycled, (e, f, d), jnp.float32)}
        return grouped_expert_ffn(h, experts, weights, ids,
                                  first=cfg.first_expert)[0]


class NemotronHMoE(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = x.reshape(-1, x.shape[-1])
        # JoyAI's router: sigmoid scores, a correction bias in the choice
        weights, ids = _Router(cfg, name="gate")(h)
        y = _Relu2Experts(cfg, name="experts")(h, weights, ids)
        if cfg.n_shared_experts:
            y = y + _Relu2MLP(cfg, cfg.shared_intermediate_size
                              * cfg.n_shared_experts, name="shared")(h)
        return y.reshape(x.shape)


class NemotronHBlock(nn.Module):
    cfg: NemotronHConfig
    layer: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        u = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        mixer = {MAMBA: lambda: GraniteMamba(cfg, name="mamba"),
                 EXPERTS: lambda: NemotronHMoE(cfg, name="moe"),
                 ATTENTION: lambda: GraniteAttention(cfg, name="attn")}
        return x + mixer[cfg.pattern[self.layer]]()(u)


class NemotronHForCausalLM(nn.Module):
    """batch {"input_ids": [B, S]} -> next-token loss; ``logits(batch)`` the
    float32 logits."""
    cfg: NemotronHConfig

    @nn.compact
    def _backbone(self, input_ids):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="embed")(input_ids)
        for i in range(cfg.num_layers):
            x = NemotronHBlock(cfg, i, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        return LMHead(cfg.hidden_size, cfg.vocab_size, cfg.dtype,
                      name="lm_head")(x).astype(jnp.float32)

    @property
    def config(self):
        return self.cfg

    def __call__(self, batch):
        input_ids = batch["input_ids"]
        logp = jax.nn.log_softmax(self._backbone(input_ids)[:, :-1], axis=-1)
        ll = jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)
        return -jnp.mean(ll)

    def logits(self, batch):
        return self._backbone(batch["input_ids"])


# ---------------------------------------------------------------------------
# HF interop
# ---------------------------------------------------------------------------
def nemotron_h_config_from_hf(hf: dict, max_seq_len: Optional[int] = None,
                              router_experts: Optional[int] = None,
                              first_expert: int = 0) -> NemotronHConfig:
    """A ``NemotronHConfig`` from the published ``config.json`` keys.
    ``max_seq_len`` bounds the positions a server admits. ``router_experts``
    is the router's width where ``n_routed_experts`` counts a held share of
    it (the router's experts ``first_expert ..``); left out, every expert is
    held. ``expand`` is not read: the mixer's inner width is
    ``mamba_num_heads x mamba_head_dim``."""
    pattern = hf["hybrid_override_pattern"]
    held = int(hf["n_routed_experts"])
    refused = [
        (len(pattern) != hf["num_hidden_layers"],
         "hybrid_override_pattern of another depth"),
        (bool(set(pattern) - {MAMBA, EXPERTS, ATTENTION}),
         f"a layer that is none of {MAMBA!r}, {EXPERTS!r}, {ATTENTION!r} in "
         f"hybrid_override_pattern (the family's dense '-' MLP layer)"),
        (hf.get("mlp_hidden_act", "relu2") != "relu2",
         "mlp_hidden_act other than relu2"),
        (hf.get("mamba_hidden_act", "silu") != "silu",
         "mamba_hidden_act other than silu"),
        (hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1,
         "n_group or topk_group other than 1 (a choice limited to groups of "
         "experts)"),
        (not hf.get("norm_topk_prob", True), "norm_topk_prob false"),
        (hf.get("attention_bias", False), "attention_bias"),
        (hf.get("mlp_bias", False), "mlp_bias"),
        (hf.get("mamba_proj_bias", False) or hf.get("use_bias", False),
         "mamba_proj_bias / use_bias"),
        (not hf.get("use_conv_bias", True), "use_conv_bias false"),
        (hf.get("tie_word_embeddings", False), "tie_word_embeddings"),
    ]
    for bad, what in refused:
        if bad:
            raise ValueError(f"unsupported nemotron_h config: {what}")
    heads = hf["num_attention_heads"]
    return NemotronHConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        pattern=pattern, num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        mamba_heads=hf["mamba_num_heads"],
        mamba_head_dim=hf["mamba_head_dim"],
        mamba_d_state=hf["ssm_state_size"], mamba_groups=hf.get("n_groups", 1),
        mamba_d_conv=hf.get("conv_kernel", 4),
        mamba_chunk_size=hf.get("chunk_size", 128),
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_intermediate_size=hf.get("moe_shared_expert_intermediate_size",
                                        hf["moe_intermediate_size"]),
        n_routed_experts=int(router_experts or held),
        n_shared_experts=hf.get("n_shared_experts", 0),
        num_experts_per_tok=hf["num_experts_per_tok"],
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        experts_held=held, first_expert=int(first_expert),
        max_seq_len=int(max_seq_len or
                        hf.get("max_position_embeddings", 4096)),
        rms_norm_eps=hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)))


#: (our name under ``mamba``, the checkpoint's under ``mixer.``)
_MAMBA_VECTORS = (("conv_bias", "conv1d.bias"), ("dt_bias", "dt_bias"),
                  ("a_log", "A_log"), ("d", "D"), ("norm", "norm.weight"))
_ATTN = (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"))


def convert_hf_nemotron_h(hf_state, cfg: NemotronHConfig):
    """Map a checkpoint's state dict into the ``NemotronHForCausalLM`` tree,
    by the published model code's names: ``backbone.embeddings``,
    ``backbone.layers.N.norm`` and ``.mixer`` (a Mamba-2 mixer's ``in_proj``,
    ``conv1d``, ``dt_bias``, ``A_log``, ``D``, ``norm``, ``out_proj``; an
    attention mixer's ``{q,k,v,o}_proj``; an expert mixer's ``gate.weight``,
    ``gate.e_score_correction_bias``, ``experts.J.{up,down}_proj`` and
    ``shared_experts.{up,down}_proj``), ``backbone.norm_f``, ``lm_head``. Of
    the routed experts the held ones alone are read (``first_expert ..``)."""
    from deepspeed_tpu.models.families import _t as t
    from deepspeed_tpu.models.families import hf_get

    def get(name):
        return hf_get(hf_state, name)

    d = cfg.hidden_size
    tree = {"embed": {"embedding": get("backbone.embeddings.weight")},
            "final_norm": {"scale": get("backbone.norm_f.weight")},
            "lm_head": {"kernel": t(get("lm_head.weight"))}}
    for i, kind in enumerate(cfg.pattern):
        p = f"backbone.layers.{i}."
        m = p + "mixer."
        layer = {"norm": {"scale": get(p + "norm.weight")}}
        if kind == MAMBA:
            layer["mamba"] = {
                "in_proj": t(get(m + "in_proj.weight")),
                # a depthwise Conv1d's weight is [C, 1, K]
                "conv_kernel": np.asarray(get(m + "conv1d.weight"))[:, 0, :],
                **{ours: get(m + theirs) for ours, theirs in _MAMBA_VECTORS},
                "out_proj": t(get(m + "out_proj.weight"))}
        elif kind == ATTENTION:
            heads = {"wq": cfg.num_heads, "wk": cfg.num_kv_heads,
                     "wv": cfg.num_kv_heads}
            layer["attn"] = {
                **{ours: {"kernel": t(get(m + f"{theirs}.weight")).reshape(
                    d, heads[ours], cfg.head_dim)} for ours, theirs in _ATTN},
                "wo": {"kernel": t(get(m + "o_proj.weight")).reshape(
                    cfg.num_heads, cfg.head_dim, d)}}
        else:
            held = range(cfg.first_expert, cfg.first_expert + cfg.held)
            layer["moe"] = {
                "gate": {"kernel": t(get(m + "gate.weight")),
                         "bias": get(m + "gate.e_score_correction_bias")},
                # up_proj.weight is [F, D] as ``w_in`` keeps it, down_proj's
                # [D, F] is transposed
                "experts": {
                    "w_in": np.stack([np.asarray(get(
                        m + f"experts.{j}.up_proj.weight")) for j in held]),
                    "w_down": np.stack([t(get(
                        m + f"experts.{j}.down_proj.weight"))
                        for j in held])}}
            if cfg.n_shared_experts:
                layer["moe"]["shared"] = {
                    ours: {"kernel": t(get(
                        m + f"shared_experts.{theirs}.weight"))}
                    for ours, theirs in (("w_up", "up_proj"),
                                         ("w_down", "down_proj"))}
        tree[f"layer_{i}"] = layer
    return tree


def export_hf_nemotron_h(params, cfg: NemotronHConfig) -> dict:
    """The inverse of ``convert_hf_nemotron_h``: the tree under the
    checkpoint's names, linears as numpy ``[out, in]``, the held experts
    under their ids among the router's."""
    def t(w):
        return np.asarray(w, np.float32).T

    out = {"backbone.embeddings.weight": np.asarray(
               params["embed"]["embedding"], np.float32),
           "backbone.norm_f.weight": np.asarray(params["final_norm"]["scale"]),
           "lm_head.weight": t(params["lm_head"]["kernel"])}
    for i, kind in enumerate(cfg.pattern):
        lp, p = params[f"layer_{i}"], f"backbone.layers.{i}."
        m = p + "mixer."
        out[p + "norm.weight"] = np.asarray(lp["norm"]["scale"])
        if kind == MAMBA:
            mp = lp["mamba"]
            out[m + "in_proj.weight"] = t(mp["in_proj"])
            out[m + "conv1d.weight"] = np.asarray(mp["conv_kernel"],
                                                  np.float32)[:, None, :]
            for ours, theirs in _MAMBA_VECTORS:
                out[m + theirs] = np.asarray(mp[ours], np.float32)
            out[m + "out_proj.weight"] = t(mp["out_proj"])
        elif kind == ATTENTION:
            ap = lp["attn"]
            for ours, theirs in _ATTN:
                out[m + f"{theirs}.weight"] = np.asarray(
                    ap[ours]["kernel"], np.float32).reshape(
                        cfg.hidden_size, -1).T
            out[m + "o_proj.weight"] = np.asarray(
                ap["wo"]["kernel"], np.float32).reshape(-1, cfg.hidden_size).T
        else:
            moe = lp["moe"]
            out[m + "gate.weight"] = t(moe["gate"]["kernel"])
            out[m + "gate.e_score_correction_bias"] = np.asarray(
                moe["gate"]["bias"], np.float32)
            for j in range(cfg.held):
                at = m + f"experts.{cfg.first_expert + j}."
                out[at + "up_proj.weight"] = np.asarray(
                    moe["experts"]["w_in"][j], np.float32)
                out[at + "down_proj.weight"] = t(moe["experts"]["w_down"][j])
            for ours, theirs in (("w_up", "up_proj"), ("w_down", "down_proj")):
                if "shared" in moe:
                    out[m + f"shared_experts.{theirs}.weight"] = \
                        t(moe["shared"][ours]["kernel"])
    return out
