"""MiMo-V2 causal LM (``model_type: mimo_v2``; MiMo-V2.5's language model,
48 layers): full and sliding-window attention layers with KV head counts of
their own, keys wider than values, a learned sink logit a head in the
windowed layers' softmax, a rope base a layer kind over a part of the head,
values scaled before they are summed, sigmoid-routed experts chosen with a
correction bias behind a leading dense layer, no shared expert.

Reference analog: none in ``deepspeed/inference/v2/model_implementations``.
The layer equations, layer ``l`` full where ``hybrid_layer_pattern[l] == 0``
and windowed where 1, its shape (``AttnShape``) ``H`` query heads over
``H_kv`` KV heads, keys ``d_k`` and values ``d_v`` wide, ``h = RMSNorm(x)``,
no bias anywhere:

- ``q = h W_q`` (``H`` x ``d_k``), ``k = h W_k`` (``H_kv`` x ``d_k``),
  ``v = attention_value_scale * (h W_v)`` (``H_kv`` x ``d_v``); query head
  ``i`` reads KV head ``i // (H / H_kv)``.
- rope in the rotate-half form over the leading ``int(partial_rotary_factor
  * d_k)`` dims of q and k (dim ``j`` pairs with ``j + r / 2``), base
  ``rope_theta`` in a full layer and ``swa_rope_theta`` in a windowed one;
  the other dims pass through.
- scores ``s_tj = q_t . k_j / sqrt(d_k)`` over ``j <= t``, in a windowed
  layer ``j > t - sliding_window`` too. A layer without sinks:
  ``p = softmax_j(s)``. With (``add_swa_attention_sink_bias`` the windowed
  layers, ``add_full_attention_sink_bias`` the full ones), head ``n`` with
  its learned ``b_n``: ``p_tj = exp(s_tj) / (exp(b_n) + sum_j' exp(s_tj'))``:
  the sink joins the denominator and has no value. ``o_t = sum_j p_tj v_j``;
  ``x += concat_heads(o) W_o``.
- ``h2 = RMSNorm(x)``; a layer with ``moe_layer_freq[l] == 0`` adds a gated
  SiLU MLP of ``intermediate_size``; every other the routed sum of
  ``models/joyai_llm_flash.py`` (``route``: sigmoid scores, the
  ``num_experts_per_tok`` largest of score + ``e_score_correction_bias``,
  weights the chosen scores normalised to sum 1 times
  ``routed_scaling_factor``) over gated MLPs of ``moe_intermediate_size``.
- head: RMSNorm, an untied ``lm_head``.

**A held share of the experts** (``experts_held``, ``first_expert``) as in
``models/laguna.py``: the router keeps its width and its top-k, an assignment
to an expert held elsewhere is left out.

Not built: the multi-token-prediction layers and the vision and audio
encoders of MiMo-V2.5 (no key of the language model's config names them; the
step programs yield one token a row from token ids). ``attention_chunk_size``
is not read: a windowed layer is a sliding window. Group-limited routing
(``n_group`` > 1) is refused by name. Serving: ``inference/v2/modules.py``
``MiMoV2Policy``, whose cache keeps pages by layer kind, each kind with its
own heads and widths (``kv_cache.py``).
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.joyai_llm_flash import (_MLP_NAMES, JoyAIFlashMoE,
                                                  _SwiGLU)
from deepspeed_tpu.models.laguna import apply_rope_half
from deepspeed_tpu.models.llama import LMHead, RMSNorm, rope_freqs


@dataclasses.dataclass(frozen=True)
class AttnShape:
    """One layer kind's attention: query heads, KV heads, key and value
    widths."""
    num_heads: int = 64
    num_kv_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class MiMoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384        # a dense layer's MLP
    moe_intermediate_size: int = 2048     # one routed expert
    full: AttnShape = AttnShape()
    swa: AttnShape = AttnShape(num_kv_heads=8)
    # 0: a full layer, 1: a windowed one; 0: a dense MLP, 1: routed experts
    hybrid_layer_pattern: Tuple[int, ...] = ((0, 1, 1, 1, 1)
                                             + (0, 1, 1, 1, 1, 1) * 7 + (0,))
    moe_layer_freq: Tuple[int, ...] = (0,) + (1,) * 47
    sliding_window: int = 128
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    n_routed_experts: int = 256           # the router's width
    n_shared_experts: int = 0
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    # the share of the router's experts stacked here: ``experts_held`` of
    # them from ``first_expert`` on (None: all)
    experts_held: Optional[int] = None
    first_expert: int = 0
    # positions the rope tables cover; a server bounds it to the contexts it
    # admits (every step program embeds the tables)
    max_seq_len: int = 1048576
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        n = len(self.hybrid_layer_pattern)
        if len(self.moe_layer_freq) != n or \
                set(self.hybrid_layer_pattern + self.moe_layer_freq) - {0, 1}:
            raise ValueError(
                f"hybrid_layer_pattern ({n} layers) and moe_layer_freq "
                f"({len(self.moe_layer_freq)}) are one 0 or 1 a layer each")
        for at in (self.full, self.swa):
            if at.num_heads % at.num_kv_heads:
                raise ValueError(f"{at.num_heads} query heads do not divide "
                                 f"over {at.num_kv_heads} KV heads")
        if not 0 < self.held <= self.n_routed_experts - self.first_expert:
            raise ValueError(f"experts {self.first_expert} .. "
                             f"{self.first_expert + self.held - 1} are not "
                             f"among the router's {self.n_routed_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_layer_pattern)

    @property
    def held(self) -> int:
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    def is_windowed(self, layer: int) -> bool:
        return self.hybrid_layer_pattern[layer] == 1

    def is_dense(self, layer: int) -> bool:
        return self.moe_layer_freq[layer] == 0

    def attn(self, layer: int) -> AttnShape:
        return self.swa if self.is_windowed(layer) else self.full

    def window(self, layer: int) -> Optional[int]:
        return self.sliding_window if self.is_windowed(layer) else None

    def has_sink(self, layer: int) -> bool:
        return self.add_swa_attention_sink_bias if self.is_windowed(layer) \
            else self.add_full_attention_sink_bias

    def rope_base(self, layer: int) -> float:
        return self.swa_rope_theta if self.is_windowed(layer) \
            else self.rope_theta

    def rotary_dim(self, layer: int) -> int:
        """Leading dims of a key that rotate: 64 of 192 at the published
        0.334, rounded down to whole pairs."""
        return int(self.partial_rotary_factor
                   * self.attn(layer).head_dim) // 2 * 2


TINY_MIMO_V2 = MiMoV2Config(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32,
    full=AttnShape(num_heads=4, num_kv_heads=1, head_dim=24, v_head_dim=16),
    swa=AttnShape(num_heads=4, num_kv_heads=2, head_dim=24, v_head_dim=16),
    hybrid_layer_pattern=(0, 1, 1, 0, 1), moe_layer_freq=(0, 1, 1, 1, 1),
    sliding_window=24, n_routed_experts=16, num_experts_per_tok=4,
    max_seq_len=512)


def rope_tables(cfg: MiMoV2Config, layer: int):
    """(cos, sin) [max_seq_len, rotary / 2] numpy tables of ``layer``'s kind."""
    return rope_freqs(cfg.rotary_dim(layer), cfg.max_seq_len,
                      cfg.rope_base(layer))


# ---------------------------------------------------------------------------
# flax modules (the plain form: no cache)
# ---------------------------------------------------------------------------
def _dense(features, name, cfg, axis=-1):
    return nn.DenseGeneral(features, axis=axis, use_bias=False,
                           dtype=cfg.dtype, param_dtype=jnp.float32, name=name)


class MiMoV2Attention(nn.Module):
    cfg: MiMoV2Config
    layer: int

    @nn.compact
    def __call__(self, x, positions):
        cfg, i = self.cfg, self.layer
        at = cfg.attn(i)
        heads, kvh = at.num_heads, at.num_kv_heads
        cos, sin = rope_tables(cfg, i)
        q = apply_rope_half(_dense((heads, at.head_dim), "wq", cfg)(x),
                            cos, sin, positions)
        k = apply_rope_half(_dense((kvh, at.head_dim), "wk", cfg)(x),
                            cos, sin, positions)
        v = _dense((kvh, at.v_head_dim), "wv", cfg)(x) \
            * jnp.asarray(cfg.attention_value_scale, cfg.dtype)
        k, v = (jnp.repeat(t, heads // kvh, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) \
            * at.head_dim ** -0.5
        qpos, kpos = positions[:, :, None], positions[:, None, :]
        seen = kpos <= qpos
        if cfg.is_windowed(i):
            seen = seen & (kpos > qpos - cfg.sliding_window)
        s = jnp.where(seen[:, None], s, -1e30)
        if cfg.has_sink(i):
            # the program's initialiser leaves the sinks at 0 (a plain
            # softmax with one more unit in the denominator)
            sink = self.param("sink", nn.initializers.zeros, (heads,),
                              jnp.float32)
            sink = jnp.broadcast_to(sink[None, :, None, None],
                                    s.shape[:3] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, sink], -1), -1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(cfg.dtype), v)
        return _dense(cfg.hidden_size, "wo", cfg, axis=(-2, -1))(o)


class MiMoV2Block(nn.Module):
    cfg: MiMoV2Config
    layer: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + MiMoV2Attention(cfg, self.layer, name="attn")(
            norm("attn_norm")(x), positions)
        h = norm("mlp_norm")(x)
        if cfg.is_dense(self.layer):
            return x + _SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
        # JoyAI's router, stacked experts and routed sum over the held share
        return x + JoyAIFlashMoE(cfg, name="moe")(h)


class MiMoV2ForCausalLM(nn.Module):
    """batch {"input_ids": [B, S]} -> next-token loss; ``logits(batch)`` the
    float32 logits."""
    cfg: MiMoV2Config

    @nn.compact
    def _backbone(self, input_ids):
        cfg = self.cfg
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]),
                                     input_ids.shape)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="embed")(input_ids)
        for i in range(cfg.num_layers):
            x = MiMoV2Block(cfg, i, name=f"layer_{i}")(x, positions)
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
def mimo_v2_config_from_hf(hf: dict, max_seq_len: Optional[int] = None,
                           router_experts: Optional[int] = None,
                           first_expert: int = 0) -> MiMoV2Config:
    """A ``MiMoV2Config`` from the published ``config.json`` keys.
    ``max_seq_len`` bounds the positions the rope tables cover (a server's
    longest context). ``router_experts`` is the router's width where
    ``n_routed_experts`` counts a chip's share of it (the experts
    ``first_expert ..``); left out, every expert is held."""
    layers = hf["num_hidden_layers"]
    pattern = tuple(hf.get("hybrid_layer_pattern") or [0] * layers)
    freq = hf.get("moe_layer_freq", 1)
    freq = tuple(freq) if isinstance(freq, (list, tuple)) \
        else tuple(int(i % freq == 0) for i in range(layers))
    scaling = hf.get("rope_scaling") or {}
    window = hf.get("sliding_window", hf.get("sliding_window_size"))
    refused = [
        (len(pattern) != layers or len(freq) != layers,
         "hybrid_layer_pattern or moe_layer_freq of another depth"),
        (hf.get("scoring_func", "sigmoid") != "sigmoid",
         f"scoring_func {hf.get('scoring_func')!r} (sigmoid only)"),
        (hf.get("topk_method", "noaux_tc") != "noaux_tc",
         f"topk_method {hf.get('topk_method')!r} (noaux_tc only)"),
        ((hf.get("n_group") or 1) != 1 or (hf.get("topk_group") or 1) != 1,
         "group-limited routing (n_group, topk_group other than 1)"),
        (not hf.get("norm_topk_prob", True), "norm_topk_prob false"),
        (hf.get("n_shared_experts"), "shared experts"),
        (scaling.get("rope_type", scaling.get("type", "default"))
         != "default", f"rope_scaling {scaling!r} (default only)"),
        (hf.get("sliding_window_size", window) != window,
         "sliding_window_size that disagrees with sliding_window"),
        (hf.get("hidden_act", "silu") != "silu", "hidden_act other than silu"),
        (hf.get("attention_bias", False), "attention_bias"),
        (hf.get("tie_word_embeddings", False), "tie_word_embeddings"),
    ]
    for bad, what in refused:
        if bad:
            raise ValueError(f"unsupported mimo_v2 config: {what}")
    full = AttnShape(hf["num_attention_heads"], hf["num_key_value_heads"],
                     hf["head_dim"], hf.get("v_head_dim", hf["head_dim"]))
    swa = AttnShape(
        hf.get("swa_num_attention_heads", full.num_heads),
        hf.get("swa_num_key_value_heads", full.num_kv_heads),
        hf.get("swa_head_dim", full.head_dim),
        hf.get("swa_v_head_dim", full.v_head_dim))
    held = int(hf["n_routed_experts"])
    return MiMoV2Config(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        full=full, swa=swa, hybrid_layer_pattern=pattern,
        moe_layer_freq=freq, sliding_window=int(window),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        swa_rope_theta=float(hf.get("swa_rope_theta",
                                    hf.get("rope_theta", 10000.0))),
        partial_rotary_factor=float(hf.get("partial_rotary_factor", 1.0)),
        attention_value_scale=float(hf.get("attention_value_scale") or 1.0),
        add_swa_attention_sink_bias=bool(
            hf.get("add_swa_attention_sink_bias", False)),
        add_full_attention_sink_bias=bool(
            hf.get("add_full_attention_sink_bias", False)),
        n_routed_experts=int(router_experts or held),
        num_experts_per_tok=hf["num_experts_per_tok"],
        routed_scaling_factor=float(hf.get("routed_scaling_factor") or 1.0),
        experts_held=held, first_expert=int(first_expert),
        max_seq_len=int(max_seq_len or
                        hf.get("max_position_embeddings", 4096)),
        rms_norm_eps=hf.get("layernorm_epsilon",
                            hf.get("rms_norm_eps", 1e-5)))


def convert_hf_mimo_v2(hf_state, cfg: MiMoV2Config):
    """Map a checkpoint's state dict into the ``MiMoV2ForCausalLM`` tree.
    The names are assumed (no checkpoint was read): DeepSeek-V3's for the
    experts (``mlp.gate.weight``, ``mlp.gate.e_score_correction_bias``,
    ``mlp.experts.<j>``), ``self_attn.{q,k,v,o}_proj`` or, where the
    checkpoint fuses them (``attention_projection_layout: fused_qkv``),
    ``self_attn.qkv_proj`` as q's rows, then k's, then v's, and a layer's
    sinks as ``self_attn.attention_sink_bias``. Of the routed experts the
    held ones alone are read (``first_expert ..``); the
    multi-token-prediction layers and the encoders are not."""
    from deepspeed_tpu.models.families import _t as t
    from deepspeed_tpu.models.families import hf_get

    def get(name):
        return hf_get(hf_state, name)

    def swiglu(prefix):
        return {ours: {"kernel": t(get(f"{prefix}{theirs}.weight"))}
                for ours, theirs in _MLP_NAMES}

    d = cfg.hidden_size
    tree = {"embed": {"embedding": get("model.embed_tokens.weight")},
            "final_norm": {"scale": get("model.norm.weight")},
            "lm_head": {"kernel": t(get("lm_head.weight"))}}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        at = cfg.attn(i)
        widths = (at.num_heads * at.head_dim, at.num_kv_heads * at.head_dim,
                  at.num_kv_heads * at.v_head_dim)
        if a + "qkv_proj.weight" in hf_state:
            wq, wk, wv = np.split(get(a + "qkv_proj.weight"),
                                  np.cumsum(widths)[:-1], axis=0)
        else:
            wq, wk, wv = (get(a + f"{n}_proj.weight") for n in "qkv")
        attn = {
            "wq": {"kernel": t(wq).reshape(d, at.num_heads, at.head_dim)},
            "wk": {"kernel": t(wk).reshape(d, at.num_kv_heads, at.head_dim)},
            "wv": {"kernel": t(wv).reshape(d, at.num_kv_heads,
                                           at.v_head_dim)},
            "wo": {"kernel": t(get(a + "o_proj.weight")).reshape(
                at.num_heads, at.v_head_dim, d)}}
        if cfg.has_sink(i):
            attn["sink"] = get(a + "attention_sink_bias").reshape(-1)
        layer = {
            "attn_norm": {"scale": get(p + "input_layernorm.weight")},
            "mlp_norm": {"scale": get(p + "post_attention_layernorm.weight")},
            "attn": attn}
        if cfg.is_dense(i):
            layer["mlp"] = swiglu(p + "mlp.")
        else:
            held = range(cfg.first_expert, cfg.first_expert + cfg.held)
            layer["moe"] = {
                "gate": {"kernel": t(get(p + "mlp.gate.weight")),
                         "bias": get(p + "mlp.gate.e_score_correction_bias")},
                "experts": {ours: np.stack([
                    t(get(p + f"mlp.experts.{j}.{theirs}.weight"))
                    for j in held]) for ours, theirs in _MLP_NAMES}}
        tree[f"layer_{i}"] = layer
    return tree


def export_hf_mimo_v2(params, cfg: MiMoV2Config) -> dict:
    """The inverse of ``convert_hf_mimo_v2`` with the projections unfused:
    the tree under the checkpoint's names, as numpy ``[out, in]`` linears;
    the held experts under their ids among the router's."""
    def t(w):
        return np.asarray(w, np.float32).T

    out = {"model.embed_tokens.weight": np.asarray(
               params["embed"]["embedding"], np.float32),
           "model.norm.weight": np.asarray(params["final_norm"]["scale"]),
           "lm_head.weight": t(params["lm_head"]["kernel"])}
    for i in range(cfg.num_layers):
        lp, p = params[f"layer_{i}"], f"model.layers.{i}."
        ap, a = lp["attn"], p + "self_attn."
        out[p + "input_layernorm.weight"] = np.asarray(lp["attn_norm"]["scale"])
        out[p + "post_attention_layernorm.weight"] = \
            np.asarray(lp["mlp_norm"]["scale"])
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj")):
            out[a + f"{theirs}.weight"] = np.asarray(
                ap[ours]["kernel"], np.float32).reshape(cfg.hidden_size, -1).T
        out[a + "o_proj.weight"] = np.asarray(
            ap["wo"]["kernel"], np.float32).reshape(-1, cfg.hidden_size).T
        if cfg.has_sink(i):
            out[a + "attention_sink_bias"] = np.asarray(ap["sink"], np.float32)
        if cfg.is_dense(i):
            for ours, theirs in _MLP_NAMES:
                out[f"{p}mlp.{theirs}.weight"] = t(lp["mlp"][ours]["kernel"])
            continue
        moe = lp["moe"]
        out[p + "mlp.gate.weight"] = t(moe["gate"]["kernel"])
        out[p + "mlp.gate.e_score_correction_bias"] = \
            np.asarray(moe["gate"]["bias"])
        for ours, theirs in _MLP_NAMES:
            for j in range(cfg.held):
                out[p + f"mlp.experts.{cfg.first_expert + j}.{theirs}"
                    ".weight"] = t(moe["experts"][ours][j])
    return out
