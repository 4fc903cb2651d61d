"""LFM2-MoE causal LM (``model_type: lfm2_moe``; LFM2-24B-A2B, 40 layers):
gated SHORT-CONVOLUTION layers with a grouped-query attention layer among
every four, a gated MLP (the leading layers) or sigmoid-routed gated experts
behind EVERY mixer, and a tied head.

Reference analog: none in ``deepspeed/inference/v2/model_implementations``.
The layer equations, ``D = hidden_size``, layer ``l`` of kind
``layer_types[l]``, RMSNorm ``x * rsqrt(mean(x^2) + norm_eps) * w``, no bias
anywhere:

- ``h_0 = E[token]``; a layer is ``h += M(RMSNorm_op(h))`` then ``h +=
  F(RMSNorm_ffn(h))``; ``logits = RMSNorm(h_L) E^T`` (the published name of
  the last norm is ``embedding_norm``; the head is the embedding).
- ``conv``: ``[B ; C ; v] = W_in u`` (``D`` each); ``g_t = B_t * v_t`` (the
  gate BEFORE the convolution); ``c_t = sum_{j < K} w[:, j] g_{t - K + 1 +
  j}``, depthwise and causal over ``K = conv_L_cache`` taps, no bias and NO
  activation (``ops/ssm.py`` ``causal_conv``, which a Mamba-2 layer follows
  with silu); ``M = W_out (C_t * c_t)``. What a sequence keeps of such a layer
  is ``g``'s last ``K - 1`` rows and nothing else: no recurrent state.
- ``full_attention``: ``num_heads`` query heads over ``num_kv_heads`` KV
  heads of ``head_dim``; q and k each RMS-normed over a head's ``head_dim``
  values (one learned scale of ``head_dim`` for q, one for k) BEFORE rope;
  rope over the whole head, rotate-half, ``rope_theta``; causal
  ``softmax(q k^T head_dim ** -0.5) v``; ``W_o``.
- ``F``, layer ``l < num_dense_layers``: ``W_2(silu(W_1 y) * W_3 y)`` of
  ``intermediate_size``. Otherwise ``s = sigmoid(W_g y)`` over
  ``num_experts`` in float32, the ``num_experts_per_tok`` largest of ``s +
  expert_bias`` chosen (the bias steers the choice only), their ``s``
  renormalised to sum to 1 (``norm_topk_prob``; the published ``sum + 1e-6``
  is ``sigmoid_route``'s ``sum + 1e-20`` here: 5e-7 of a weight) times
  ``routed_scaling_factor``, and the chosen experts' gated MLPs of
  ``moe_intermediate_size`` summed (``moe/grouped_experts.py``). No shared
  expert.

What is refused by name (``lfm2_moe_config_from_hf``): ``conv_bias: true``, a
``layer_types`` entry that is neither ``conv`` nor ``full_attention``,
``norm_topk_prob`` or ``use_expert_bias`` false, any rope scaling, an untied
head. Serving: ``inference/v2/modules.py`` ``Lfm2MoePolicy``, whose cache
keeps the attention layers' pages and the conv layers' TAILS by layer kind
(``kv_cache._TailSlots``).
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.granite_hybrid import _conv_init
from deepspeed_tpu.models.joyai_llm_flash import _Experts, _SwiGLU
from deepspeed_tpu.models.llama import RMSNorm, apply_rope, rope_freqs
from deepspeed_tpu.moe.grouped_experts import sigmoid_route
from deepspeed_tpu.ops import ssm

CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776        # a leading dense layer's MLP
    moe_intermediate_size: int = 1536     # one routed expert
    layer_types: Tuple[str, ...] = ((CONV, CONV, ATTENTION, CONV) * 10)
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    conv_width: int = 3                   # ``conv_L_cache``: taps
    num_experts: int = 64
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    routed_scaling_factor: float = 1.0
    # positions the rope tables cover; a server bounds it to the contexts it
    # admits (every step program embeds the tables: 256 bytes a position)
    max_seq_len: int = 128000
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.layer_types) - {CONV, ATTENTION} \
                or self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"a layer type is neither {CONV!r} nor {ATTENTION!r}, or "
                f"{self.num_heads} query heads do not divide over "
                f"{self.num_kv_heads} KV heads")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def is_conv(self, layer: int) -> bool:
        return self.layer_types[layer] == CONV

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    # every routed expert is stacked here and none is shared: what
    # ``joyai_llm_flash._Experts`` and the served ``_dense_or_experts`` read
    # from whichever config they are given
    @property
    def first_expert(self) -> int:
        return 0

    @property
    def held(self) -> int:
        return self.num_experts

    @property
    def n_shared_experts(self) -> int:
        return 0

    @property
    def kv_pack(self) -> int:
        """KV heads whose keys (and values) a page's row holds side by side
        (``GraniteHybridConfig.kv_pack``: two heads of 64 fill 128 lanes)."""
        pack = max(128 // self.head_dim, 1)
        return pack if self.num_kv_heads % pack == 0 else 1


TINY_LFM2_MOE = Lfm2MoeConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32,
    layer_types=(CONV, CONV, ATTENTION, CONV, CONV, ATTENTION),
    num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
    num_experts_per_tok=2, num_dense_layers=2, max_seq_len=512,
    rope_theta=10000.0)


def route(h, moe, cfg: Lfm2MoeConfig):
    """(weights [T, K] float32, expert ids [T, K]) from an expert layer's
    router parameters ``moe["gate"]`` (``kernel`` [D, E], ``bias`` [E], the
    published ``expert_bias``)."""
    return sigmoid_route(h, moe["gate"]["kernel"], moe["gate"]["bias"],
                         cfg.num_experts_per_tok, cfg.routed_scaling_factor)


def head_norm(x, scale, eps):
    """RMSNorm over each head's values. x: [..., heads, head_dim]; scale:
    [head_dim], one for all heads."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def short_conv_sequence(cp, u, cfg: Lfm2MoeConfig):
    """The gated short convolution over one whole sequence from a zero tail
    (the plain form: no cache). cp: the layer's ``conv`` parameters; u: [T,
    D]."""
    dtype = cfg.dtype
    b, c, v = jnp.split(u @ cp["in_proj"].astype(dtype), 3, axis=-1)
    tail = jnp.zeros((cfg.conv_width - 1, cfg.hidden_size), dtype)
    conv, _ = ssm.causal_conv(b * v, tail, cp["conv_kernel"],
                              activation=None)
    return (c * conv.astype(dtype)) @ cp["out_proj"].astype(dtype)


# ---------------------------------------------------------------------------
# flax modules (the plain form: no cache)
# ---------------------------------------------------------------------------
def _dense(features, name, cfg, axis=-1):
    return nn.DenseGeneral(features, axis=axis, use_bias=False,
                           dtype=cfg.dtype, param_dtype=jnp.float32, name=name)


class Lfm2ShortConv(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        d, k = cfg.hidden_size, cfg.conv_width
        inits = {"in_proj": (nn.initializers.lecun_normal(), (d, 3 * d)),
                 "conv_kernel": (_conv_init(k), (d, k)),
                 "out_proj": (nn.initializers.lecun_normal(), (d, d))}
        cp = {name: self.param(name, init, shape, jnp.float32)
              for name, (init, shape) in inits.items()}
        return jax.vmap(lambda row: short_conv_sequence(cp, row, cfg))(u)


class Lfm2Attention(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        heads, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        ones = nn.initializers.ones
        q = head_norm(_dense((heads, d), "wq", cfg)(x),
                      self.param("q_norm", ones, (d,), jnp.float32),
                      cfg.rms_norm_eps)
        k = head_norm(_dense((kvh, d), "wk", cfg)(x),
                      self.param("k_norm", ones, (d,), jnp.float32),
                      cfg.rms_norm_eps)
        v = _dense((kvh, d), "wv", cfg)(x)
        cos, sin = (jnp.asarray(t) for t in
                    rope_freqs(d, cfg.max_seq_len, cfg.rope_theta))
        q, k = apply_rope(q, cos, sin, positions), \
            apply_rope(k, cos, sin, positions)
        k, v = (jnp.repeat(t, heads // kvh, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * d ** -0.5
        t = x.shape[1]
        seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(cfg.dtype), v)
        return _dense(cfg.hidden_size, "wo", cfg, axis=(-2, -1))(o)


class _Router(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (cfg.hidden_size, cfg.num_experts), jnp.float32)
        # a checkpoint's expert_bias is learned by the balancing rule; a
        # random model draws one wide enough to move the choice
        # (``joyai_llm_flash._Router``)
        bias = self.param("bias", nn.initializers.normal(0.1),
                          (cfg.num_experts,), jnp.float32)
        return route(h, {"gate": {"kernel": kernel, "bias": bias}}, cfg)


class Lfm2MoE(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        h = x.reshape(-1, x.shape[-1])
        weights, ids = _Router(self.cfg, name="gate")(h)
        return _Experts(self.cfg, name="experts")(h, weights, ids).reshape(
            x.shape)


class Lfm2MoeBlock(nn.Module):
    cfg: Lfm2MoeConfig
    layer: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        h = norm("mixer_norm")(x)
        x = x + (Lfm2ShortConv(cfg, name="conv")(h) if cfg.is_conv(self.layer)
                 else Lfm2Attention(cfg, name="attn")(h, positions))
        h = norm("mlp_norm")(x)
        if cfg.is_dense(self.layer):
            return x + _SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
        return x + Lfm2MoE(cfg, name="moe")(h)


class Lfm2MoeForCausalLM(nn.Module):
    """batch {"input_ids": [B, S]} -> next-token loss; ``logits(batch)`` the
    float32 logits."""
    cfg: Lfm2MoeConfig

    @nn.compact
    def _backbone(self, input_ids):
        cfg = self.cfg
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]),
                                     input_ids.shape)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="embed")
        x = embed(input_ids)
        for i in range(cfg.num_layers):
            x = Lfm2MoeBlock(cfg, i, name=f"layer_{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        return embed.attend(x).astype(jnp.float32)

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
def lfm2_moe_config_from_hf(hf: dict, max_seq_len: Optional[int] = None
                            ) -> Lfm2MoeConfig:
    """An ``Lfm2MoeConfig`` from the published ``config.json`` keys.
    ``max_seq_len`` bounds the positions a server admits."""
    layers = hf["num_hidden_layers"]
    types = tuple(hf.get("layer_types") or [ATTENTION] * layers)
    heads = hf["num_attention_heads"]
    rope = hf.get("rope_parameters") or {}
    unknown = sorted(set(types) - {CONV, ATTENTION})
    refused = [
        (hf.get("conv_bias", False), "conv_bias true"),
        (bool(unknown), f"layer_types entries {unknown} (only {CONV!r} and "
                        f"{ATTENTION!r})"),
        (len(types) != layers, "layer_types of another depth"),
        (not hf.get("norm_topk_prob", True), "norm_topk_prob false"),
        (not hf.get("use_expert_bias", True), "use_expert_bias false"),
        (rope.get("rope_type", "default") != "default"
         or hf.get("rope_scaling") is not None,
         f"rope scaling {rope.get('rope_type') or hf.get('rope_scaling')!r}"),
        (not hf.get("tie_word_embeddings", hf.get("tie_embedding", True)),
         "an untied head"),
    ]
    for bad, what in refused:
        if bad:
            raise ValueError(f"unsupported lfm2_moe config: {what}")
    return Lfm2MoeConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        layer_types=types, num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        conv_width=hf.get("conv_L_cache", 3),
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        num_dense_layers=hf.get("num_dense_layers", 0),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        max_seq_len=int(max_seq_len or
                        hf.get("max_position_embeddings", 4096)),
        rope_theta=float(rope.get("rope_theta", hf.get("rope_theta", 1e6))),
        rms_norm_eps=hf.get("norm_eps", 1e-5))


#: (our name, the checkpoint's) of a gated MLP's three matrices, dense layer
#: and expert alike
_MLP_NAMES = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))


def convert_hf_lfm2_moe(hf_state, cfg: Lfm2MoeConfig):
    """Map a checkpoint's state dict into the ``Lfm2MoeForCausalLM`` tree, by
    the published model code's names as this builder knows them:
    ``operator_norm``, ``ffn_norm``, ``conv.{in_proj, conv, out_proj}``
    (``in_proj``'s rows ``[B ; C ; x]``), ``self_attn.{q,k,v}_proj``,
    ``self_attn.out_proj``, ``self_attn.{q,k}_layernorm``,
    ``feed_forward.{w1, w3, w2}`` (a dense layer), ``feed_forward.gate``,
    ``feed_forward.expert_bias`` and ``feed_forward.experts.J.{w1, w3, w2}``
    (an expert layer), ``embedding_norm``; the head is the embedding."""
    from deepspeed_tpu.models.families import _t as t
    from deepspeed_tpu.models.families import hf_get

    def get(name):
        return hf_get(hf_state, name)

    d = cfg.hidden_size
    tree = {"embed": {"embedding": get("model.embed_tokens.weight")},
            "final_norm": {"scale": get("model.embedding_norm.weight")}}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        layer = {"mixer_norm": {"scale": get(p + "operator_norm.weight")},
                 "mlp_norm": {"scale": get(p + "ffn_norm.weight")}}
        if cfg.is_conv(i):
            layer["conv"] = {
                "in_proj": t(get(p + "conv.in_proj.weight")),
                # a depthwise Conv1d's weight is [C, 1, K]
                "conv_kernel": np.asarray(get(p + "conv.conv.weight"))[:, 0],
                "out_proj": t(get(p + "conv.out_proj.weight"))}
        else:
            a = p + "self_attn."
            heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
            layer["attn"] = {
                "wq": {"kernel": t(get(a + "q_proj.weight")).reshape(
                    d, heads, hd)},
                "wk": {"kernel": t(get(a + "k_proj.weight")).reshape(
                    d, kvh, hd)},
                "wv": {"kernel": t(get(a + "v_proj.weight")).reshape(
                    d, kvh, hd)},
                "wo": {"kernel": t(get(a + "out_proj.weight")).reshape(
                    heads, hd, d)},
                "q_norm": get(a + "q_layernorm.weight"),
                "k_norm": get(a + "k_layernorm.weight")}
        f = p + "feed_forward."
        if cfg.is_dense(i):
            layer["mlp"] = {ours: {"kernel": t(get(f + f"{theirs}.weight"))}
                            for ours, theirs in _MLP_NAMES}
        else:
            layer["moe"] = {
                "gate": {"kernel": t(get(f + "gate.weight")),
                         "bias": get(f + "expert_bias")},
                "experts": {ours: np.stack(
                    [t(get(f + f"experts.{j}.{theirs}.weight"))
                     for j in range(cfg.num_experts)])
                    for ours, theirs in _MLP_NAMES}}
        tree[f"layer_{i}"] = layer
    return tree


def export_hf_lfm2_moe(params, cfg: Lfm2MoeConfig) -> dict:
    """The inverse of ``convert_hf_lfm2_moe``: the tree under the
    checkpoint's names, linears as numpy ``[out, in]``."""
    def t(w):
        return np.asarray(w, np.float32).T

    out = {"model.embed_tokens.weight": np.asarray(
               params["embed"]["embedding"], np.float32),
           "model.embedding_norm.weight":
               np.asarray(params["final_norm"]["scale"])}
    for i in range(cfg.num_layers):
        lp, p = params[f"layer_{i}"], f"model.layers.{i}."
        out[p + "operator_norm.weight"] = np.asarray(lp["mixer_norm"]["scale"])
        out[p + "ffn_norm.weight"] = np.asarray(lp["mlp_norm"]["scale"])
        if cfg.is_conv(i):
            cp = lp["conv"]
            out[p + "conv.in_proj.weight"] = t(cp["in_proj"])
            out[p + "conv.conv.weight"] = np.asarray(
                cp["conv_kernel"], np.float32)[:, None, :]
            out[p + "conv.out_proj.weight"] = t(cp["out_proj"])
        else:
            ap, a = lp["attn"], p + "self_attn."
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj")):
                out[a + f"{theirs}.weight"] = np.asarray(
                    ap[ours]["kernel"], np.float32).reshape(
                        cfg.hidden_size, -1).T
            out[a + "out_proj.weight"] = np.asarray(
                ap["wo"]["kernel"], np.float32).reshape(-1, cfg.hidden_size).T
            out[a + "q_layernorm.weight"] = np.asarray(ap["q_norm"])
            out[a + "k_layernorm.weight"] = np.asarray(ap["k_norm"])
        f = p + "feed_forward."
        if cfg.is_dense(i):
            for ours, theirs in _MLP_NAMES:
                out[f + f"{theirs}.weight"] = t(lp["mlp"][ours]["kernel"])
            continue
        moe = lp["moe"]
        out[f + "gate.weight"] = t(moe["gate"]["kernel"])
        out[f + "expert_bias"] = np.asarray(moe["gate"]["bias"], np.float32)
        for j in range(cfg.num_experts):
            for ours, theirs in _MLP_NAMES:
                out[f + f"experts.{j}.{theirs}.weight"] = \
                    t(moe["experts"][ours][j])
    return out
