"""Granite 4.0-H causal LM (``model_type: granitemoehybrid``; granite-4.0-h-
micro, 40 layers): Mamba-2 state-space layers with a softmax-attention layer
among every ten, a shared gated MLP after every mixer, scalar multipliers on
the embedding, the residual branches, the attention scores and the logits,
and no positional embedding at all.

Reference analog: none in ``deepspeed/inference/v2/model_implementations``.
The layer equations, ``d = hidden_size``, layer ``l`` of kind
``layer_types[l]``:

- ``h_0 = embedding_multiplier E[token]``; a layer is ``h += r M(RMSNorm(h))``
  then ``h += r F(RMSNorm(h))`` with ``r = residual_multiplier``, ``M`` the
  layer's mixer and ``F(u) = W_out(silu(g) * v)``, ``[g ; v] = W_in u``
  (``shared_intermediate_size``; no bias). ``logits = RMSNorm(h_L) E^T /
  logits_scaling`` (tied).
- ``attention``: ``num_heads`` query heads over ``num_kv_heads`` KV heads of
  ``head_dim``, no bias, no rotary or other position (``nope``), causal
  ``softmax(attention_multiplier q k^T) v``, ``W_o``.
- ``mamba`` (Mamba-2, ``ops/ssm.py``): ``[z ; xBC ; dt] = W_in u`` (widths
  ``d_inner``, ``d_inner + 2 n_groups d_state``, ``mamba_heads``); ``xBC``
  through a causal depthwise convolution of ``d_conv`` taps with bias, then
  silu; ``[x ; B ; C] = xBC``; a head's step ``softplus(dt + dt_bias)``,
  decay ``exp(step A)``, ``A = -exp(a_log)``; ``S_t = a_t S_{t-1} + step_t x_t
  (x) B_t``, ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm(y * silu(z))`` over all
  of ``d_inner`` (the gate before the norm, one group); ``W_o``.

What is refused by name (``granite_hybrid_config_from_hf``): routed experts
(``num_local_experts > 0``, the family's larger models), more than one group
of ``B`` and ``C``, any positional embedding, biases. Serving:
``inference/v2/modules.py`` ``GraniteHybridPolicy``, whose cache keeps the
attention layers' pages and the Mamba layers' states by layer kind
(``kv_cache.py``).
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import RMSNorm
from deepspeed_tpu.ops import ssm

ATTENTION, MAMBA = "attention", "mamba"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192         # the shared MLP
    layer_types: Tuple[str, ...] = ((MAMBA,) * 5 + (ATTENTION,)
                                    + (MAMBA,) * 4) * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    max_seq_len: int = 131072
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.layer_types) - {ATTENTION, MAMBA} \
                or self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"a layer type is neither {ATTENTION!r} nor {MAMBA!r}, or "
                f"{self.num_heads} query heads do not divide over "
                f"{self.num_kv_heads} KV heads")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    # one group of B and C, one group in the gated norm: what the mixer
    # below reads from whichever config it is given (``NemotronHConfig``
    # states eight of each)
    @property
    def mamba_groups(self) -> int:
        return 1

    @property
    def norm_groups(self) -> int:
        return 1

    @property
    def conv_channels(self) -> int:
        """``[x ; B ; C]``: what the convolution runs over."""
        return self.d_inner + 2 * self.mamba_groups * self.mamba_d_state

    @property
    def in_proj_width(self) -> int:
        return self.d_inner + self.conv_channels + self.mamba_heads

    def is_mamba(self, layer: int) -> bool:
        return self.layer_types[layer] == MAMBA

    @property
    def kv_pack(self) -> int:
        """KV heads whose keys (and values) a page's row holds side by side:
        a row narrower than the TPU's 128 lanes is padded to them in memory,
        so two heads of 64 share one (``GraniteHybridPolicy``)."""
        pack = max(128 // self.head_dim, 1)
        return pack if self.num_kv_heads % pack == 0 else 1


TINY_GRANITE_HYBRID = GraniteHybridConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA),
    num_heads=4, num_kv_heads=2, head_dim=64, mamba_heads=8,
    mamba_head_dim=8, mamba_d_state=16, mamba_chunk_size=16, max_seq_len=512)


def split_in_proj(zxbcdt, cfg: GraniteHybridConfig):
    """(z [.., d_inner], xBC [.., conv_channels], dt [.., heads]) of the
    mixer's first projection."""
    a, b = cfg.d_inner, cfg.d_inner + cfg.conv_channels
    return zxbcdt[..., :a], zxbcdt[..., a:b], zxbcdt[..., b:]


def gated_norm(y, z, scale, eps, groups: int = 1):
    """``RMSNorm(y * silu(z))``: the gate before the norm, each of ``groups``
    equal parts of the width normalised alone (one: the whole width), one
    learned scale over all of it."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(*g.shape[:-1], groups, -1)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), -1, keepdims=True) + eps)
    return (parts.reshape(g.shape) * scale).astype(y.dtype)


def mamba_sequence(mp, u, cfg: GraniteHybridConfig):
    """The mixer over one whole sequence from a zero state (the plain form:
    no cache). mp: the layer's ``mamba`` parameters; u: [T, D]."""
    dtype = cfg.dtype
    zxbcdt = u @ mp["in_proj"].astype(dtype)
    z, xbc, dt = split_in_proj(zxbcdt, cfg)
    tail = jnp.zeros((cfg.mamba_d_conv - 1, cfg.conv_channels), dtype)
    conv, _ = ssm.causal_conv(xbc, tail, mp["conv_kernel"], mp["conv_bias"])
    conv = conv.astype(dtype)
    x, bm, cm = ssm.split_conv(conv, cfg.mamba_heads, cfg.mamba_head_dim,
                               cfg.mamba_d_state, cfg.mamba_groups)
    step = jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"])
    s0 = jnp.zeros((cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state))
    y, _ = ssm.ssm_chunk_scan(x, step, mp["a_log"], bm, cm, s0,
                              cfg.mamba_chunk_size)
    y = (y + mp["d"][:, None] * x).reshape(-1, cfg.d_inner).astype(dtype)
    y = gated_norm(y, z, mp["norm"], cfg.rms_norm_eps, cfg.norm_groups)
    return y @ mp["out_proj"].astype(dtype)


# ---------------------------------------------------------------------------
# initialisers: the Mamba-2 reference implementation's defaults, which the
# published config leaves unstated
# ---------------------------------------------------------------------------
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_RANGE = (1.0, 16.0)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -exp(a_log)`` with ``exp(a_log)`` uniform in ``A_RANGE``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, *A_RANGE))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniform in [DT_MIN, DT_MAX]
    and floored at DT_FLOOR."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype)
                   * (np.log(DT_MAX) - np.log(DT_MIN)) + np.log(DT_MIN))
    step = jnp.maximum(step, DT_FLOOR)
    return step + jnp.log(-jnp.expm1(-step))


def _conv_init(taps: int):
    """A depthwise Conv1d's default draw, weight [C, K] and bias [C] alike:
    uniform within ``taps ** -0.5``."""
    def init(key, shape, dtype=jnp.float32):
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


# ---------------------------------------------------------------------------
# flax modules (the plain form: no cache)
# ---------------------------------------------------------------------------
def _dense(features, name, cfg, axis=-1):
    return nn.DenseGeneral(features, axis=axis, use_bias=False,
                           dtype=cfg.dtype, param_dtype=jnp.float32, name=name)


class GraniteMamba(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        h, c, k = cfg.mamba_heads, cfg.conv_channels, cfg.mamba_d_conv
        inits = {
            "in_proj": (nn.initializers.lecun_normal(),
                        (cfg.hidden_size, cfg.in_proj_width)),
            "conv_kernel": (_conv_init(k), (c, k)),
            "conv_bias": (_conv_init(k), (c,)),
            "dt_bias": (_dt_bias_init, (h,)),
            "a_log": (_a_log_init, (h,)),
            "d": (nn.initializers.ones, (h,)),
            "norm": (nn.initializers.ones, (cfg.d_inner,)),
            "out_proj": (nn.initializers.lecun_normal(),
                         (cfg.d_inner, cfg.hidden_size))}
        mp = {name: self.param(name, init, shape, jnp.float32)
              for name, (init, shape) in inits.items()}
        return jax.vmap(lambda row: mamba_sequence(mp, row, cfg))(u)


class GraniteAttention(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        heads, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _dense((heads, d), "wq", cfg)(x)
        k = _dense((kvh, d), "wk", cfg)(x)
        v = _dense((kvh, d), "wv", cfg)(x)
        k, v = (jnp.repeat(t, heads // kvh, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) \
            * cfg.attention_multiplier
        t = x.shape[1]
        seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(cfg.dtype), v)
        return _dense(cfg.hidden_size, "wo", cfg, axis=(-2, -1))(o)


class _SwiGLU(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        g = nn.silu(_dense(cfg.intermediate_size, "w_gate", cfg)(x))
        u = _dense(cfg.intermediate_size, "w_up", cfg)(x)
        return _dense(cfg.hidden_size, "w_down", cfg)(g * u)


class GraniteHybridBlock(nn.Module):
    cfg: GraniteHybridConfig
    layer: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        h = norm("mixer_norm")(x)
        mixed = GraniteMamba(cfg, name="mamba")(h) if cfg.is_mamba(self.layer) \
            else GraniteAttention(cfg, name="attn")(h)
        x = x + cfg.residual_multiplier * mixed
        return x + cfg.residual_multiplier * _SwiGLU(cfg, name="mlp")(
            norm("mlp_norm")(x))


class GraniteHybridForCausalLM(nn.Module):
    """batch {"input_ids": [B, S]} -> next-token loss; ``logits(batch)`` the
    float32 logits."""
    cfg: GraniteHybridConfig

    @nn.compact
    def _backbone(self, input_ids):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="embed")
        x = embed(input_ids) * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
        for i in range(cfg.num_layers):
            x = GraniteHybridBlock(cfg, i, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        return embed.attend(x).astype(jnp.float32) / cfg.logits_scaling

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
def granite_hybrid_config_from_hf(hf: dict, max_seq_len: Optional[int] = None
                                  ) -> GraniteHybridConfig:
    """A ``GraniteHybridConfig`` from the published ``config.json`` keys.
    ``max_seq_len`` bounds the positions a server admits."""
    layers = hf["num_hidden_layers"]
    types = tuple(hf.get("layer_types") or [ATTENTION] * layers)
    heads = hf["num_attention_heads"]
    d_inner = hf.get("mamba_expand", 2) * hf["hidden_size"]
    mamba_heads = hf.get("mamba_n_heads", 0)
    refused = [
        (hf.get("num_local_experts", 0) > 0,
         f"num_local_experts {hf.get('num_local_experts')}: routed experts "
         f"beside state-space layers (the family's larger models)"),
        (len(types) != layers, "layer_types of another depth"),
        (hf.get("position_embedding_type", "nope") != "nope",
         f"position_embedding_type {hf.get('position_embedding_type')!r} "
         f"(only 'nope': no positional embedding)"),
        (hf.get("mamba_n_groups", 1) != 1, "mamba_n_groups other than 1"),
        (MAMBA in types and mamba_heads * hf.get("mamba_d_head", 0) != d_inner,
         "mamba_n_heads x mamba_d_head other than mamba_expand x hidden_size"),
        (hf.get("attention_bias", False), "attention_bias"),
        (hf.get("mamba_proj_bias", False), "mamba_proj_bias"),
        (MAMBA in types and not hf.get("mamba_conv_bias", True),
         "mamba_conv_bias false"),
        (not hf.get("tie_word_embeddings", True),
         "tie_word_embeddings false"),
        (hf.get("hidden_act", "silu") != "silu", "hidden_act other than silu"),
        (hf.get("normalization_function", "rmsnorm") != "rmsnorm",
         "normalization_function other than rmsnorm"),
    ]
    for bad, what in refused:
        if bad:
            raise ValueError(f"unsupported granitemoehybrid config: {what}")
    return GraniteHybridConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf.get("shared_intermediate_size",
                                 hf.get("intermediate_size")),
        layer_types=types, num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        mamba_heads=mamba_heads, mamba_head_dim=hf.get("mamba_d_head", 0),
        mamba_d_state=hf.get("mamba_d_state", 0),
        mamba_d_conv=hf.get("mamba_d_conv", 4),
        mamba_chunk_size=hf.get("mamba_chunk_size", 256),
        embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(hf.get("residual_multiplier", 1.0)),
        attention_multiplier=float(hf.get("attention_multiplier", 1.0)),
        logits_scaling=float(hf.get("logits_scaling", 1.0)),
        max_seq_len=int(max_seq_len or
                        hf.get("max_position_embeddings", 4096)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5))


#: (our name under ``mamba``, the checkpoint's under ``mamba.``)
_MAMBA_VECTORS = (("conv_bias", "conv1d.bias"), ("dt_bias", "dt_bias"),
                  ("a_log", "A_log"), ("d", "D"))


def convert_hf_granite_hybrid(hf_state, cfg: GraniteHybridConfig):
    """Map a checkpoint's state dict into the ``GraniteHybridForCausalLM``
    tree, by the published names: ``self_attn.{q,k,v,o}_proj``,
    ``mamba.{in_proj,conv1d,dt_bias,A_log,D,norm,out_proj}``,
    ``shared_mlp.input_linear`` (``[gate ; up]`` fused on its rows) and
    ``shared_mlp.output_linear``; the head is the embedding."""
    from deepspeed_tpu.models.families import _t as t
    from deepspeed_tpu.models.families import hf_get

    def get(name):
        return hf_get(hf_state, name)

    d, f = cfg.hidden_size, cfg.intermediate_size
    tree = {"embed": {"embedding": get("model.embed_tokens.weight")},
            "final_norm": {"scale": get("model.norm.weight")}}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        fused = np.asarray(get(p + "shared_mlp.input_linear.weight"))
        layer = {
            "mixer_norm": {"scale": get(p + "input_layernorm.weight")},
            "mlp_norm": {"scale": get(p + "post_attention_layernorm.weight")},
            "mlp": {"w_gate": {"kernel": t(fused[:f])},
                    "w_up": {"kernel": t(fused[f:])},
                    "w_down": {"kernel": t(get(
                        p + "shared_mlp.output_linear.weight"))}}}
        if cfg.is_mamba(i):
            m = p + "mamba."
            layer["mamba"] = {
                "in_proj": t(get(m + "in_proj.weight")),
                # a depthwise Conv1d's weight is [C, 1, K]
                "conv_kernel": np.asarray(get(m + "conv1d.weight"))[:, 0, :],
                **{ours: get(m + theirs) for ours, theirs in _MAMBA_VECTORS},
                "norm": get(m + "norm.weight"),
                "out_proj": t(get(m + "out_proj.weight"))}
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
                "wo": {"kernel": t(get(a + "o_proj.weight")).reshape(
                    heads, hd, d)}}
        tree[f"layer_{i}"] = layer
    return tree


def export_hf_granite_hybrid(params, cfg: GraniteHybridConfig) -> dict:
    """The inverse of ``convert_hf_granite_hybrid``: the tree under the
    checkpoint's names, linears as numpy ``[out, in]``."""
    def t(w):
        return np.asarray(w, np.float32).T

    out = {"model.embed_tokens.weight": np.asarray(
               params["embed"]["embedding"], np.float32),
           "model.norm.weight": np.asarray(params["final_norm"]["scale"])}
    for i in range(cfg.num_layers):
        lp, p = params[f"layer_{i}"], f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = \
            np.asarray(lp["mixer_norm"]["scale"])
        out[p + "post_attention_layernorm.weight"] = \
            np.asarray(lp["mlp_norm"]["scale"])
        out[p + "shared_mlp.input_linear.weight"] = np.concatenate(
            [t(lp["mlp"]["w_gate"]["kernel"]), t(lp["mlp"]["w_up"]["kernel"])])
        out[p + "shared_mlp.output_linear.weight"] = \
            t(lp["mlp"]["w_down"]["kernel"])
        if cfg.is_mamba(i):
            mp, m = lp["mamba"], p + "mamba."
            out[m + "in_proj.weight"] = t(mp["in_proj"])
            out[m + "conv1d.weight"] = np.asarray(mp["conv_kernel"],
                                                  np.float32)[:, None, :]
            for ours, theirs in _MAMBA_VECTORS:
                out[m + theirs] = np.asarray(mp[ours], np.float32)
            out[m + "norm.weight"] = np.asarray(mp["norm"])
            out[m + "out_proj.weight"] = t(mp["out_proj"])
            continue
        ap, a = lp["attn"], p + "self_attn."
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj")):
            out[a + f"{theirs}.weight"] = np.asarray(
                ap[ours]["kernel"], np.float32).reshape(cfg.hidden_size, -1).T
        out[a + "o_proj.weight"] = np.asarray(
            ap["wo"]["kernel"], np.float32).reshape(-1, cfg.hidden_size).T
    return out
