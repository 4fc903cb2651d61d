"""Laguna causal LM (``model_type: laguna``; Laguna-S-2.1, 48 layers): full
and sliding-window attention layers of different query-head counts over the
same KV heads, a sigmoid gate on every head's output, YaRN rope on a part of
the head in full layers and plain rope in sliding ones, softmax-routed
experts with one shared expert behind a leading dense layer.

Reference analog: none in ``deepspeed/inference/v2/model_implementations``.
The layer equations, layer ``l`` of kind ``layer_types[l]`` with ``H_l =
heads_per_layer[l]`` query heads over ``num_kv_heads`` KV heads of
``head_dim``, ``h = RMSNorm(x)``, no bias anywhere:

- ``q = h W_q`` (``H_l`` heads), ``k = h W_k``, ``v = h W_v``; query head
  ``i`` reads KV head ``i // (H_l / num_kv_heads)``.
- rope in the rotate-half form over the layer kind's rotary part (``RopeSpec``:
  sliding layers all of the head at theta 10,000; full layers the leading half
  under YaRN at theta 500,000, the rest passing through).
- causal softmax attention at ``1 / sqrt(head_dim)``; in a sliding layer key
  ``j`` is seen by query ``t`` only if ``t - sliding_window < j <= t``.
- ``g = sigmoid(h W_g)`` (``W_g``: hidden x ``H_l``), head ``i``'s output
  times ``g_i``, then ``x += concat(a) W_o``.
- ``h2 = RMSNorm(x)``; a layer in ``mlp_only_layers`` adds a gated MLP of
  ``intermediate_size``; every other ``sum_k w_k E_k(h2) + S(h2)`` with
  ``p = softmax(h2 W_r)`` in float32 over all ``num_experts``, the
  ``num_experts_per_tok`` largest (ties to the lower id), ``w =
  routed_scaling_factor * p_sel / sum p_sel``, ``E`` and the shared ``S``
  gated MLPs of ``moe_intermediate_size``.
- head: RMSNorm, an untied ``lm_head``.

**A held share of the experts.** ``experts_held`` of the router's
``num_experts`` are stacked here, the router's experts ``first_expert ..``:
one chip's share under expert parallelism. The router keeps its width and its
top-k; an assignment to an expert held elsewhere is left out, and that part
of the sum is the other chip's to add (``moe/grouped_experts.py``). The
shared expert is whole on every chip. Serving:
``inference/v2/modules.py`` ``LagunaPolicy``, whose cache keeps pages by
layer kind (``kv_cache.py``).
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.joyai_llm_flash import _upcycled
from deepspeed_tpu.models.llama import (LMHead, RMSNorm, YarnScaling,
                                        rope_freqs)
from deepspeed_tpu.moe.grouped_experts import grouped_expert_ffn, softmax_route

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One layer kind's rope: ``theta``, the share of the head that rotates,
    YaRN scaling or None."""
    theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    yarn: Optional[YarnScaling] = None


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288        # a dense layer's MLP
    moe_intermediate_size: int = 1024     # one routed expert
    shared_expert_intermediate_size: int = 1024
    num_kv_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING) * 12
    heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12
    mlp_only_layers: Tuple[int, ...] = (0,)
    sliding_window: int = 512
    num_experts: int = 256                # the router's width
    num_experts_per_tok: int = 10
    routed_scaling_factor: float = 2.5
    # the share of the router's experts stacked here: ``experts_held`` of
    # them from ``first_expert`` on (None: all)
    experts_held: Optional[int] = None
    first_expert: int = 0
    rope_full: RopeSpec = RopeSpec(
        500000.0, 0.5, YarnScaling(128.0, 8192, 32.0, 1.0,
                                   1.4852030263919618))
    rope_sliding: RopeSpec = RopeSpec()
    # positions the rope tables cover; a server bounds it to the contexts it
    # admits (every step program embeds the tables)
    max_seq_len: int = 1048576
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        n = len(self.layer_types)
        if len(self.heads_per_layer) != n:
            raise ValueError(f"{len(self.heads_per_layer)} head counts for "
                             f"{n} layer types")
        bad = [h for h in self.heads_per_layer if h % self.num_kv_heads]
        if bad or set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"head counts {bad} do not divide over "
                             f"{self.num_kv_heads} KV heads, or a layer type "
                             f"is neither {FULL!r} nor {SLIDING!r}")
        held = self.held
        if not 0 < held <= self.num_experts - self.first_expert:
            raise ValueError(f"experts {self.first_expert} .. "
                             f"{self.first_expert + held - 1} are not among "
                             f"the router's {self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    def is_sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING

    def is_dense(self, layer: int) -> bool:
        return layer in self.mlp_only_layers

    def window(self, layer: int) -> Optional[int]:
        return self.sliding_window if self.is_sliding(layer) else None

    def rope(self, layer: int) -> RopeSpec:
        return self.rope_sliding if self.is_sliding(layer) else self.rope_full


TINY_LAGUNA = LagunaConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_kv_heads=2, head_dim=16,
    layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
    heads_per_layer=(4, 6, 6, 6, 4), sliding_window=24, num_experts=16,
    num_experts_per_tok=4,
    rope_full=RopeSpec(500000.0, 0.5, YarnScaling(8.0, 64, 32.0, 1.0, None)),
    max_seq_len=512)


def rope_tables(cfg: LagunaConfig, layer: int):
    """(cos, sin) [max_seq_len, rotary / 2] numpy tables of ``layer``'s kind."""
    spec = cfg.rope(layer)
    return rope_freqs(int(cfg.head_dim * spec.partial_rotary_factor),
                      cfg.max_seq_len, spec.theta, spec.yarn)


def apply_rope_half(x, cos, sin, positions):
    """Rotate-half rope over the leading ``2 * cos.shape[-1]`` dims of ``x``
    [..., H, d] at ``positions`` [...]; the dims past them pass through."""
    rot = 2 * cos.shape[-1]
    cos_p = jnp.asarray(cos)[positions][..., None, :]
    sin_p = jnp.asarray(sin)[positions][..., None, :]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos_p - x2 * sin_p, x2 * cos_p + x1 * sin_p],
                          -1).astype(x.dtype)
    return jnp.concatenate([out, x[..., rot:]], -1)


def route(h, moe, cfg: LagunaConfig):
    """(weights [T, K] float32 times ``routed_scaling_factor``, expert ids
    [T, K] among the router's ``num_experts``)."""
    w, ids = softmax_route(h, moe["gate"]["kernel"], cfg.num_experts_per_tok,
                           True)
    return w * cfg.routed_scaling_factor, ids


# ---------------------------------------------------------------------------
# flax modules (the plain form: no cache)
# ---------------------------------------------------------------------------
def _dense(features, name, cfg, axis=-1):
    return nn.DenseGeneral(features, axis=axis, use_bias=False,
                           dtype=cfg.dtype, param_dtype=jnp.float32, name=name)


class LagunaAttention(nn.Module):
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, x, positions):
        cfg, i = self.cfg, self.layer
        heads, kvh, d = cfg.heads_per_layer[i], cfg.num_kv_heads, cfg.head_dim
        cos, sin = rope_tables(cfg, i)
        q = apply_rope_half(_dense((heads, d), "wq", cfg)(x), cos, sin,
                            positions)
        k = apply_rope_half(_dense((kvh, d), "wk", cfg)(x), cos, sin,
                            positions)
        v = _dense((kvh, d), "wv", cfg)(x)
        gate = jax.nn.sigmoid(_dense(heads, "wg", cfg)(x).astype(jnp.float32))
        k, v = (jnp.repeat(t, heads // kvh, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * d ** -0.5
        qpos, kpos = positions[:, :, None], positions[:, None, :]
        seen = kpos <= qpos
        if cfg.is_sliding(i):
            seen = seen & (kpos > qpos - cfg.sliding_window)
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(cfg.dtype), v)
        o = o * gate[..., None].astype(cfg.dtype)
        return _dense(cfg.hidden_size, "wo", cfg, axis=(-2, -1))(o)


class _SwiGLU(nn.Module):
    cfg: LagunaConfig
    width: int

    @nn.compact
    def __call__(self, x):
        g = nn.silu(_dense(self.width, "w_gate", self.cfg)(x))
        u = _dense(self.width, "w_up", self.cfg)(x)
        return _dense(self.cfg.hidden_size, "w_down", self.cfg)(g * u)


class _Router(nn.Module):
    cfg: LagunaConfig

    @nn.compact
    def __call__(self, h):
        kernel = self.param(
            "kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal"),
            (self.cfg.hidden_size, self.cfg.num_experts), jnp.float32)
        return route(h, {"gate": {"kernel": kernel}}, self.cfg)


class _Experts(nn.Module):
    """The stacked weights of the routed experts held here."""
    cfg: LagunaConfig

    @nn.compact
    def __call__(self, h, weights, ids):
        cfg = self.cfg
        e, d, f = cfg.held, cfg.hidden_size, cfg.moe_intermediate_size
        # drawn as sparse upcycling leaves experts (``_upcycled``): a router
        # near-tie broken the other way by bfloat16 then moves a token's
        # state by a part of an expert, not by one
        experts = {
            "w_gate": self.param("w_gate", _upcycled, (e, d, f), jnp.float32),
            "w_up": self.param("w_up", _upcycled, (e, d, f), jnp.float32),
            "w_down": self.param("w_down", _upcycled, (e, f, d), jnp.float32)}
        return grouped_expert_ffn(h, experts, weights, ids,
                                  first=cfg.first_expert)[0]


class LagunaMoE(nn.Module):
    cfg: LagunaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = x.reshape(-1, x.shape[-1])
        weights, ids = _Router(cfg, name="gate")(h)
        y = _Experts(cfg, name="experts")(h, weights, ids)
        y = y + _SwiGLU(cfg, cfg.shared_expert_intermediate_size,
                        name="shared")(h)
        return y.reshape(x.shape)


class LagunaBlock(nn.Module):
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + LagunaAttention(cfg, self.layer, name="attn")(
            norm("attn_norm")(x), positions)
        h = norm("mlp_norm")(x)
        if cfg.is_dense(self.layer):
            return x + _SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
        return x + LagunaMoE(cfg, name="moe")(h)


class LagunaForCausalLM(nn.Module):
    """batch {"input_ids": [B, S]} -> next-token loss; ``logits(batch)`` the
    float32 logits."""
    cfg: LagunaConfig

    @nn.compact
    def _backbone(self, input_ids):
        cfg = self.cfg
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]),
                                     input_ids.shape)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="embed")(input_ids)
        for i in range(cfg.num_layers):
            x = LagunaBlock(cfg, i, name=f"layer_{i}")(x, positions)
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
def _rope_spec(params: dict) -> RopeSpec:
    kind = params.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"unsupported laguna config: rope_type {kind!r} "
                         f"(default or yarn)")
    yarn = None
    if kind == "yarn":
        yarn = YarnScaling(
            float(params["factor"]),
            int(params["original_max_position_embeddings"]),
            float(params.get("beta_fast", 32)),
            float(params.get("beta_slow", 1)),
            params.get("attention_factor"))
    return RopeSpec(float(params.get("rope_theta", 10000.0)),
                    float(params.get("partial_rotary_factor", 1.0)), yarn)


def laguna_config_from_hf(hf: dict, max_seq_len: Optional[int] = None,
                          router_experts: Optional[int] = None,
                          first_expert: int = 0) -> LagunaConfig:
    """A ``LagunaConfig`` from the published ``config.json`` keys.
    ``max_seq_len`` bounds the positions the rope tables cover (a server's
    longest context). ``router_experts`` is the router's width where
    ``num_experts`` counts a chip's share of it (the experts ``first_expert
    ..``); left out, every expert is held."""
    layers = hf["num_hidden_layers"]
    types = tuple(hf.get("layer_types") or [FULL] * layers)
    heads = tuple(hf.get("num_attention_heads_per_layer")
                  or [hf["num_attention_heads"]] * layers)
    mlp_types = hf.get("mlp_layer_types")
    dense = tuple(hf.get("mlp_only_layers") or ())
    refused = [
        (len(types) != layers or len(heads) != layers,
         "layer_types or num_attention_heads_per_layer of another depth"),
        (mlp_types is not None and
         [i for i, t in enumerate(mlp_types) if t == "dense"] != list(dense),
         "mlp_layer_types that disagree with mlp_only_layers"),
        (hf.get("decoder_sparse_step", 1) != 1, "decoder_sparse_step other "
         "than 1"),
        (not hf.get("norm_topk_prob", True), "norm_topk_prob false"),
        (hf.get("moe_router_logit_softcapping", 0),
         "moe_router_logit_softcapping"),
        (hf.get("moe_apply_router_weight_on_input", False),
         "moe_apply_router_weight_on_input"),
        (hf.get("gating", "per-head") != "per-head" or
         set(hf.get("gating_types") or ["per_head"]) != {"per_head"},
         "a gate other than per-head"),
        (hf.get("attention_bias", False), "attention_bias"),
        (hf.get("tie_word_embeddings", False), "tie_word_embeddings"),
    ]
    for bad, what in refused:
        if bad:
            raise ValueError(f"unsupported laguna config: {what}")
    rope = hf["rope_parameters"]
    held = int(hf["num_experts"])
    return LagunaConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_intermediate_size=hf["shared_expert_intermediate_size"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        layer_types=types, heads_per_layer=heads, mlp_only_layers=dense,
        sliding_window=int(hf["sliding_window"]),
        num_experts=int(router_experts or held),
        num_experts_per_tok=hf["num_experts_per_tok"],
        routed_scaling_factor=float(hf.get("moe_routed_scaling_factor", 1.0)),
        experts_held=held, first_expert=int(first_expert),
        rope_full=_rope_spec(rope[FULL]),
        rope_sliding=_rope_spec(rope[SLIDING]),
        max_seq_len=int(max_seq_len or
                        hf.get("max_position_embeddings", 4096)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6))


#: (our name in a gated MLP, the checkpoint's)
_MLP_NAMES = (("w_gate", "gate_proj"), ("w_up", "up_proj"),
              ("w_down", "down_proj"))


def convert_hf_laguna(hf_state, cfg: LagunaConfig):
    """Map a checkpoint's state dict into the ``LagunaForCausalLM`` tree.
    The names are assumed to follow the code family the config's keys are of
    (Qwen2-MoE's: ``self_attn.{q,k,v,o}_proj``, ``mlp.gate`` the router,
    ``mlp.experts.<j>``, ``mlp.shared_expert``), with the per-head gate as
    ``self_attn.g_proj``. Of the routed experts the held ones alone are read
    (``first_expert ..``)."""
    from deepspeed_tpu.models.families import _t as t
    from deepspeed_tpu.models.families import hf_get

    def get(name):
        return hf_get(hf_state, name)

    def swiglu(prefix):
        return {ours: {"kernel": t(get(f"{prefix}{theirs}.weight"))}
                for ours, theirs in _MLP_NAMES}

    d, kvh, hd = cfg.hidden_size, cfg.num_kv_heads, cfg.head_dim
    tree = {"embed": {"embedding": get("model.embed_tokens.weight")},
            "final_norm": {"scale": get("model.norm.weight")},
            "lm_head": {"kernel": t(get("lm_head.weight"))}}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        heads = cfg.heads_per_layer[i]
        layer = {
            "attn_norm": {"scale": get(p + "input_layernorm.weight")},
            "mlp_norm": {"scale": get(p + "post_attention_layernorm.weight")},
            "attn": {
                "wq": {"kernel": t(get(a + "q_proj.weight")).reshape(
                    d, heads, hd)},
                "wk": {"kernel": t(get(a + "k_proj.weight")).reshape(
                    d, kvh, hd)},
                "wv": {"kernel": t(get(a + "v_proj.weight")).reshape(
                    d, kvh, hd)},
                "wg": {"kernel": t(get(a + "g_proj.weight"))},
                "wo": {"kernel": t(get(a + "o_proj.weight")).reshape(
                    heads, hd, d)}}}
        if cfg.is_dense(i):
            layer["mlp"] = swiglu(p + "mlp.")
        else:
            held = range(cfg.first_expert, cfg.first_expert + cfg.held)
            layer["moe"] = {
                "gate": {"kernel": t(get(p + "mlp.gate.weight"))},
                "experts": {ours: np.stack([
                    t(get(p + f"mlp.experts.{j}.{theirs}.weight"))
                    for j in held]) for ours, theirs in _MLP_NAMES},
                "shared": swiglu(p + "mlp.shared_expert.")}
        tree[f"layer_{i}"] = layer
    return tree


def export_hf_laguna(params, cfg: LagunaConfig) -> dict:
    """The inverse of ``convert_hf_laguna``: the tree under the checkpoint's
    names, as numpy ``[out, in]`` linears; the held experts under their ids
    among the router's."""
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
        out[a + "g_proj.weight"] = t(ap["wg"]["kernel"])
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
        for ours, theirs in _MLP_NAMES:
            for j in range(cfg.held):
                out[p + f"mlp.experts.{cfg.first_expert + j}.{theirs}"
                    ".weight"] = t(moe["experts"][ours][j])
        swiglu(p + "mlp.shared_expert.", moe["shared"])
    return out
