"""JoyAI-LLM-Flash causal LM (``model_type: joyai_llm_flash``, 48B-A2.7B):
latent attention (MLA) with a low-rank query, sigmoid-routed experts chosen
with a correction bias (``noaux_tc``), one shared expert, a leading dense
layer.

Reference analog: none in ``deepspeed/inference/v2/model_implementations``
(the reference serves no latent-attention model). The layer equations, with
``h = RMSNorm(x)``:

- attention, head ``i``: ``cq = RMSNorm(W_dq h)``; ``[q_nope_i ; q_rope_i] =
  W_uq_i cq``; ``[ckv ; kr] = W_dkv h``, ``ckv = RMSNorm(ckv)``; ``q_rope_i``
  and the one ``kr`` all heads share are rotated (pairs ``(2j, 2j+1)``);
  ``[k_nope_i ; v_i] = W_ukv_i ckv``; scores ``(q_nope_i . k_nope_i + q_rope_i
  . kr) / sqrt(d_nope + d_rope)``, causal softmax, ``x += W_o [o_1 .. o_H]``.
  What a cache keeps is ``(ckv, kr)``, one row a token with no heads. This
  module's plain forward up-projects keys and values; serving
  (``inference/v2/modules.py`` ``JoyAIFlashPolicy``) caches the rows.
- layers below ``first_k_dense_replace``: a gated MLP of ``intermediate_size``.
- the others: ``moe/grouped_experts.py`` (sigmoid scores, the ``top_k`` with
  the largest score + bias, weights ``routed_scaling_factor * s / sum s``)
  plus one gated MLP of ``n_shared_experts * moe_intermediate_size`` on every
  token.
- head: RMSNorm, an untied ``lm_head`` in float32.

The multi-token-prediction module (``num_nextn_predict_layers``) adds nothing
to the model's logits and is not built or loaded. Group-limited routing
(``n_group`` > 1) is refused by name. ``rope_scaling`` of type ``yarn`` is
DeepSeek-V3's: the rotary part's frequencies are YaRN's (``models/llama.py``
``YarnScaling``), cos and sin are multiplied by ``mscale(mscale) /
mscale(mscale_all_dim)`` and the softmax scale by ``mscale(mscale_all_dim)``
squared, ``mscale(m) = 0.1 m ln(factor) + 1``.
"""

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import (LMHead, RMSNorm, YarnScaling,
                                        rope_freqs)
from deepspeed_tpu.moe.grouped_experts import grouped_expert_ffn, sigmoid_route


@dataclasses.dataclass(frozen=True)
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168         # the leading dense layer's MLP
    moe_intermediate_size: int = 768      # one routed expert; the shared one
    num_layers: int = 40
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 1
    # positions the rope tables cover; a server bounds it to the contexts it
    # admits (every step program embeds the tables: 512 bytes a position)
    max_seq_len: int = 131072
    rope_theta: float = 32e6
    # YaRN over the rotary part (``rope_scaling``), its ``attention_factor``
    # what multiplies cos and sin; ``yarn_mscale_all_dim`` enters the softmax
    # scale (module docstring)
    rope_yarn: Optional[YarnScaling] = None
    yarn_mscale_all_dim: float = 0.0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """One cached row: the normed compressed kv and the rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        if self.rope_yarn is None:
            return scale
        return scale * yarn_mscale(self.rope_yarn.factor,
                                   self.yarn_mscale_all_dim) ** 2

    # every routed expert is stacked here. ``_Experts`` and the served
    # ``_dense_or_experts`` read these two from whichever config they are
    # given (``MiMoV2Config`` states a held share of its own)
    @property
    def first_expert(self) -> int:
        return 0

    @property
    def held(self) -> int:
        return self.n_routed_experts

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V3's ``yarn_get_mscale``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


TINY_JOYAI_FLASH = JoyAIFlashConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_layers=4, num_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    n_routed_experts=16, num_experts_per_tok=4, max_seq_len=512,
    rope_theta=10000.0)


def apply_rope_pairs(x, positions, cfg: JoyAIFlashConfig):
    """Rotate the pairs ``(2j, 2j+1)`` of ``x``'s last axis (``rope_interleave``)
    by ``positions * theta ** (-2j / d)``, or by YaRN's frequencies where the
    config has ``rope_yarn``. ``positions`` has ``x``'s leading axes, or those
    up to a heads axis that is broadcast (``x`` [..., H, d], positions
    [...])."""
    cos, sin = rope_freqs(x.shape[-1], cfg.max_seq_len, cfg.rope_theta,
                          cfg.rope_yarn)
    cos, sin = jnp.asarray(cos)[positions], jnp.asarray(sin)[positions]
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :], sin[..., None, :]
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def route(h, moe, cfg: JoyAIFlashConfig):
    """(weights [T, K] float32, expert ids [T, K]) from an expert layer's
    router parameters ``moe["gate"]`` (``kernel`` [D, E], ``bias`` [E])."""
    return sigmoid_route(h, moe["gate"]["kernel"], moe["gate"]["bias"],
                         cfg.num_experts_per_tok, cfg.routed_scaling_factor)


# ---------------------------------------------------------------------------
# flax modules
# ---------------------------------------------------------------------------
def _dense(features, name, cfg, axis=-1):
    return nn.DenseGeneral(features, axis=axis, use_bias=False,
                           dtype=cfg.dtype, param_dtype=jnp.float32, name=name)


class JoyAIFlashAttention(nn.Module):
    """The plain form: keys and values up-projected per head."""
    cfg: JoyAIFlashConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        heads, d_n, d_v = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
        cq = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(
            _dense(cfg.q_lora_rank, "wq_a", cfg)(x))
        q = _dense((heads, cfg.qk_head_dim), "wq_b", cfg)(cq)
        ckv = _dense(cfg.latent_dim, "wkv_a", cfg)(x)
        c = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="kv_norm")(
            ckv[..., :cfg.kv_lora_rank])
        kv = _dense((heads, d_n + d_v), "wkv_b", cfg)(c)
        k_r = apply_rope_pairs(ckv[..., cfg.kv_lora_rank:], positions, cfg)
        q = jnp.concatenate(
            [q[..., :d_n], apply_rope_pairs(q[..., d_n:], positions, cfg)], -1)
        k = jnp.concatenate(
            [kv[..., :d_n], jnp.broadcast_to(
                k_r[:, :, None, :], kv.shape[:3] + (cfg.qk_rope_head_dim,))],
            -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * cfg.softmax_scale
        seq = x.shape[1]
        causal = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(cfg.dtype), kv[..., d_n:])
        return _dense(cfg.hidden_size, "wo", cfg, axis=(-2, -1))(o)


class _SwiGLU(nn.Module):
    cfg: JoyAIFlashConfig
    width: int

    @nn.compact
    def __call__(self, x):
        g = nn.silu(_dense(self.width, "w_gate", self.cfg)(x))
        u = _dense(self.width, "w_up", self.cfg)(x)
        return _dense(self.cfg.hidden_size, "w_down", self.cfg)(g * u)


class _Router(nn.Module):
    cfg: JoyAIFlashConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (cfg.hidden_size, cfg.n_routed_experts),
                            jnp.float32)
        # the checkpoint's e_score_correction_bias is learned by the
        # balancing rule; a random model draws one wide enough to move the
        # choice (scores lie about 0.005 apart around the eighth largest)
        bias = self.param("bias", nn.initializers.normal(0.1),
                          (cfg.n_routed_experts,), jnp.float32)
        return route(h, {"gate": {"kernel": kernel, "bias": bias}}, cfg)


#: how far a layer's routed experts lie apart at initialisation, as a share
#: of what they have in common (``_upcycled``)
EXPERT_SPREAD = 1 / 16


def _upcycled(key, shape, dtype=jnp.float32):
    """Initialiser of a stack of experts [E, in, out] as sparse upcycling
    leaves one: a draw that all share (a gated MLP's usual) plus
    ``EXPERT_SPREAD`` times a draw of each expert's own. What the chosen
    experts compute then weighs what a sublayer does times their weights'
    sum (``routed_scaling_factor``), so their arithmetic shows in the logits,
    while two experts differ by a small part of that: where bfloat16 states
    break a near-tie in the router the other way than float32 ones (at the
    published sizes the ninth largest score of 256 lies within such noise of
    the eighth in most tokens of a few layers), the token's state moves by
    that small part and not by an expert."""
    draw = nn.initializers.lecun_normal()
    own = nn.initializers.lecun_normal(batch_axis=(0,))
    k_shared, k_own = jax.random.split(key)
    return draw(k_shared, shape[1:], dtype)[None] \
        + EXPERT_SPREAD * own(k_own, shape, dtype)


class _Experts(nn.Module):
    """The stacked weights of the routed experts held here."""
    cfg: JoyAIFlashConfig

    @nn.compact
    def __call__(self, h, weights, ids):
        cfg = self.cfg
        e, d, f = cfg.held, cfg.hidden_size, cfg.moe_intermediate_size
        experts = {
            "w_gate": self.param("w_gate", _upcycled, (e, d, f), jnp.float32),
            "w_up": self.param("w_up", _upcycled, (e, d, f), jnp.float32),
            "w_down": self.param("w_down", _upcycled, (e, f, d), jnp.float32)}
        return grouped_expert_ffn(h, experts, weights, ids,
                                  first=cfg.first_expert)[0]


class JoyAIFlashMoE(nn.Module):
    cfg: JoyAIFlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = x.reshape(-1, x.shape[-1])
        weights, ids = _Router(cfg, name="gate")(h)
        y = _Experts(cfg, name="experts")(h, weights, ids)
        if cfg.n_shared_experts:
            y = y + _SwiGLU(cfg, cfg.moe_intermediate_size *
                            cfg.n_shared_experts, name="shared")(h)
        return y.reshape(x.shape)


class JoyAIFlashBlock(nn.Module):
    cfg: JoyAIFlashConfig
    layer: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + JoyAIFlashAttention(cfg, name="attn")(
            norm("attn_norm")(x), positions)
        h = norm("mlp_norm")(x)
        if cfg.is_dense(self.layer):
            return x + _SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
        return x + JoyAIFlashMoE(cfg, name="moe")(h)


class JoyAIFlashForCausalLM(nn.Module):
    """batch {"input_ids": [B, S]} -> next-token loss; ``logits(batch)`` the
    float32 logits."""
    cfg: JoyAIFlashConfig

    @nn.compact
    def _backbone(self, input_ids):
        cfg = self.cfg
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]),
                                     input_ids.shape)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="embed")(input_ids)
        for i in range(cfg.num_layers):
            x = JoyAIFlashBlock(cfg, i, name=f"layer_{i}")(x, positions)
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
# HF interop (weight names follow the DeepSeek-V3 layout)
# ---------------------------------------------------------------------------
def _yarn_from_hf(scaling: Optional[dict], family: str):
    """(``YarnScaling`` or None, ``mscale_all_dim``) from ``rope_scaling``."""
    if scaling is None:
        return None, 0.0
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn" or "original_max_position_embeddings" not in scaling:
        raise ValueError(
            f"unsupported {family} config: rope_scaling {scaling!r} (yarn "
            f"with factor and original_max_position_embeddings only)")
    factor = float(scaling["factor"])
    all_dim = float(scaling.get("mscale_all_dim", 0.0))
    return YarnScaling(
        factor, int(scaling["original_max_position_embeddings"]),
        float(scaling.get("beta_fast", 32.0)),
        float(scaling.get("beta_slow", 1.0)),
        yarn_mscale(factor, float(scaling.get("mscale", 1.0)))
        / yarn_mscale(factor, all_dim)), all_dim


def latent_moe_fields(hf: dict, max_seq_len: Optional[int] = None,
                      family: str = "joyai_llm_flash") -> dict:
    """The fields of a ``JoyAIFlashConfig`` from the published
    ``config.json`` keys (DeepSeek-V3's), for this family and for the ones
    that extend its config. ``max_seq_len`` bounds the positions the rope
    tables cover (a server's longest context); the published
    ``max_position_embeddings`` otherwise."""
    refused = [
        (hf.get("moe_layer_freq", 1) != 1, "moe_layer_freq other than 1"),
        (hf.get("scoring_func", "sigmoid") != "sigmoid",
         f"scoring_func {hf.get('scoring_func')!r} (sigmoid only)"),
        (hf.get("topk_method", "noaux_tc") != "noaux_tc",
         f"topk_method {hf.get('topk_method')!r} (noaux_tc only)"),
        ((hf.get("n_group") or 1) != 1 or (hf.get("topk_group") or 1) != 1,
         "group-limited routing (n_group, topk_group other than 1)"),
        (not hf.get("norm_topk_prob", True), "norm_topk_prob false"),
        (not hf.get("rope_interleave", True), "rope_interleave false"),
        (not hf.get("q_lora_rank"), "a full-rank query (no q_lora_rank)"),
        (hf.get("attention_bias", False), "attention_bias"),
        (hf.get("tie_word_embeddings", False), "tie_word_embeddings"),
    ]
    for bad, what in refused:
        if bad:
            raise ValueError(f"unsupported {family} config: {what}")
    yarn, all_dim = _yarn_from_hf(hf.get("rope_scaling"), family)
    return dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        n_routed_experts=hf["n_routed_experts"],
        n_shared_experts=hf.get("n_shared_experts") or 0,
        num_experts_per_tok=hf["num_experts_per_tok"],
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        first_k_dense_replace=hf.get("first_k_dense_replace", 0),
        max_seq_len=int(max_seq_len or
                        hf.get("max_position_embeddings", 4096)),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_yarn=yarn, yarn_mscale_all_dim=all_dim,
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6))


def joyai_flash_config_from_hf(hf: dict,
                               max_seq_len: Optional[int] = None
                               ) -> JoyAIFlashConfig:
    """A ``JoyAIFlashConfig`` from the published ``config.json`` keys
    (``latent_moe_fields``)."""
    return JoyAIFlashConfig(**latent_moe_fields(hf, max_seq_len))


#: (our name in a gated MLP, the checkpoint's)
_MLP_NAMES = (("w_gate", "gate_proj"), ("w_up", "up_proj"),
              ("w_down", "down_proj"))


def convert_hf_joyai_flash(hf_state, cfg: JoyAIFlashConfig):
    """Map a checkpoint's state dict (DeepSeek-V3 names: ``q_a_proj``,
    ``kv_a_proj_with_mqa``, ``kv_b_proj``, ``mlp.gate.e_score_correction_bias``,
    ``mlp.shared_experts``) into the ``JoyAIFlashForCausalLM`` tree: torch
    ``[out, in]`` linears become ``[in, out]`` kernels, the per-head
    projections DenseGeneral kernels, the experts' weights stacks ``[E, ...]``.
    The multi-token-prediction layer's weights (``model.layers.<depth>``) are
    not read."""
    from deepspeed_tpu.models.families import _t as t
    from deepspeed_tpu.models.families import hf_get

    def get(name):
        return hf_get(hf_state, name)

    def swiglu(prefix):
        return {ours: {"kernel": t(get(f"{prefix}{theirs}.weight"))}
                for ours, theirs in _MLP_NAMES}

    d, heads = cfg.hidden_size, cfg.num_heads
    tree = {"embed": {"embedding": get("model.embed_tokens.weight")},
            "final_norm": {"scale": get("model.norm.weight")},
            "lm_head": {"kernel": t(get("lm_head.weight"))}}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        layer = {
            "attn_norm": {"scale": get(p + "input_layernorm.weight")},
            "mlp_norm": {"scale": get(p + "post_attention_layernorm.weight")},
            "attn": {
                "wq_a": {"kernel": t(get(a + "q_a_proj.weight"))},
                "q_norm": {"scale": get(a + "q_a_layernorm.weight")},
                "wq_b": {"kernel": t(get(a + "q_b_proj.weight")).reshape(
                    cfg.q_lora_rank, heads, cfg.qk_head_dim)},
                "wkv_a": {"kernel": t(get(a + "kv_a_proj_with_mqa.weight"))},
                "kv_norm": {"scale": get(a + "kv_a_layernorm.weight")},
                "wkv_b": {"kernel": t(get(a + "kv_b_proj.weight")).reshape(
                    cfg.kv_lora_rank, heads,
                    cfg.qk_nope_head_dim + cfg.v_head_dim)},
                "wo": {"kernel": t(get(a + "o_proj.weight")).reshape(
                    heads, cfg.v_head_dim, d)}}}
        if cfg.is_dense(i):
            layer["mlp"] = swiglu(p + "mlp.")
        else:
            layer["moe"] = {
                "gate": {"kernel": t(get(p + "mlp.gate.weight")),
                         "bias": get(p + "mlp.gate.e_score_correction_bias")},
                "experts": {
                    ours: np.stack([
                        t(get(p + f"mlp.experts.{j}.{theirs}.weight"))
                        for j in range(cfg.n_routed_experts)])
                    for ours, theirs in _MLP_NAMES}}
            if cfg.n_shared_experts:
                layer["moe"]["shared"] = swiglu(p + "mlp.shared_experts.")
        tree[f"layer_{i}"] = layer
    return tree


def export_hf_joyai_flash(params, cfg: JoyAIFlashConfig) -> dict:
    """The inverse of ``convert_hf_joyai_flash``: the tree under the
    checkpoint's names, as numpy ``[out, in]`` linears."""
    def t(w):
        return np.asarray(w, np.float32).T

    def flat(w, rows):
        return np.asarray(w, np.float32).reshape(rows, -1).T

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
        out[a + "q_a_proj.weight"] = t(ap["wq_a"]["kernel"])
        out[a + "q_a_layernorm.weight"] = np.asarray(ap["q_norm"]["scale"])
        out[a + "q_b_proj.weight"] = flat(ap["wq_b"]["kernel"],
                                          cfg.q_lora_rank)
        out[a + "kv_a_proj_with_mqa.weight"] = t(ap["wkv_a"]["kernel"])
        out[a + "kv_a_layernorm.weight"] = np.asarray(ap["kv_norm"]["scale"])
        out[a + "kv_b_proj.weight"] = flat(ap["wkv_b"]["kernel"],
                                           cfg.kv_lora_rank)
        out[a + "o_proj.weight"] = np.asarray(
            ap["wo"]["kernel"], np.float32).reshape(-1, cfg.hidden_size).T

        def swiglu(prefix, mlp):
            for ours, theirs in _MLP_NAMES:
                out[f"{prefix}{theirs}.weight"] = t(mlp[ours]["kernel"])
        if cfg.is_dense(i):
            swiglu(p + "mlp.", lp["mlp"])
            continue
        moe = lp["moe"]
        out[p + "mlp.gate.weight"] = t(moe["gate"]["kernel"])
        out[p + "mlp.gate.e_score_correction_bias"] = \
            np.asarray(moe["gate"]["bias"])
        for ours, theirs in _MLP_NAMES:
            for j in range(cfg.n_routed_experts):
                out[p + f"mlp.experts.{j}.{theirs}.weight"] = \
                    t(moe["experts"][ours][j])
        if cfg.n_shared_experts:
            swiglu(p + "mlp.shared_experts.", moe["shared"])
    return out
