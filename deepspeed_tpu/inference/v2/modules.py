"""Inference v2 module system: per-arch decode policies + registry + heuristics.

Reference analog: ``deepspeed/inference/v2/modules/`` (pluggable layer
implementations behind interfaces + ``module_registry.py`` + ``heuristics.py:36``)
and ``model_implementations/{llama_v2,mistral,mixtral,opt,phi3,qwen_v2,falcon}``.

TPU shape: a *policy* is a small class of pure static methods over the training
model's param pytree — no module surgery, no containers. The generic paged
serving loop (``generic_decode.py``) owns the step, the pool's page kind
(``kv_cache.py``) the cache writes, block tables and paged attention; the
policy contributes exactly the three arch-specific pieces:

- ``embed(params, tokens, positions, cfg)``          -> [N, ...] the state
  (hidden states [N, D] in most policies; opaque to the loop, which hands
  it from block to block and indexes its leading axis alone: the policy owns
  the residual, and ``Xing4Policy`` keeps several streams a row, [N, n, D])
- ``block(params, i, x, attend, positions, cfg, valid)`` -> (state, counts)
  (one layer; calls ``attend(q, k, v)`` for cache write + paged attention;
  ``valid`` [N] marks the rows that are no bucket padding; ``counts`` is
  ``None``, or for a layer that counts inside the step program an int32
  vector of ``telemetry/names.py`` ``STEP_COUNTER_ARGS``, which the loop sums
  over the layers and hands out beside the logits)
- ``unembed(params, x, cfg)``                        -> [N, V] fp32 logits
  from the state's rows

plus ``cache_spec(cfg)`` so the engine can size the paged KV pool, and which
selects the page kind. What ``attend`` takes is the kind's contract: over a
latent cache (``latent_dim``) the block calls
``attend(q_nope, q_rope, row, w_ukv, scale)`` with one row a token. Policies are
keyed both by name and by config dataclass type; ``policy_for`` is the
heuristic (reference heuristics.py) that picks the implementation for a model
config. mistral/qwen2/phi3 are LlamaConfig variants and route to LlamaPolicy.
"""

import dataclasses
import functools
from typing import Any, Callable, Dict, Type

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.llama_decode import _mlp, _qkv, _rms
from deepspeed_tpu.models.llama import LlamaConfig, rope_freqs
from deepspeed_tpu.moe.grouped_experts import (grouped_expert_ffn, relu2,
                                               softmax_route)

DECODE_POLICIES: Dict[str, type] = {}
_CONFIG_TO_POLICY: Dict[type, type] = {}


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """What a policy's cache holds, stated once: ``num_kv_heads`` keys and
    values of ``head_dim`` a token a layer behind one ``window``, unless
    ``kind_pages`` (with ``layer_windows``) states each layer kind's own, or
    ``latent_dim`` a headless row."""
    num_layers: int
    num_kv_heads: int
    head_dim: int
    max_seq_len: int
    dtype: Any
    window: Any = None       # sliding-window width or None
    # a latent (MLA) page kind: one plane with no heads, one row of
    # ``latent_dim`` values a token (``[ckv ; k_rope]``), all of it the key
    # of every query head and its leading compressed part the value
    # (``num_kv_heads`` 1, ``head_dim`` the row). 0: K and V planes
    latent_dim: int = 0
    # every layer's window (None: a full layer) for a model that mixes full
    # and windowed layers: the cache then keeps pages by layer kind
    # (``kv_cache._LayerKindPages``), the windowed kind behind its own
    # window. None: all layers' pages are of one kind behind ``window``
    layer_windows: Any = None
    # {"full": kv_cache.HeadPageShape, "window": ...}: each kind's own KV
    # heads and key and value widths, where the kinds differ or a key is
    # not as wide as a value. None: both kinds hold ``num_kv_heads`` x
    # ``head_dim``
    kind_pages: Any = None
    # query heads a layer (``{"full": n, "window": m}`` where the kinds
    # differ): the paged kernel folds the query heads of a KV head into its
    # rows, so the cache's count of a call's tiles and copies needs them.
    # None: as many as KV heads
    query_heads: Any = None
    # every layer's kind by name (``"full"``, ``"window"``, ``"state"``,
    # ``"none"``) for a model some of whose layers keep a recurrent state in
    # a slot and no pages, or nothing at all (a layer that is experts alone),
    # and what such a slot holds of a layer (``kv_cache.StateSlotShape``).
    # None: the kinds follow from ``layer_windows``
    layer_kinds: Any = None
    state_slot: Any = None


def register_policy(name: str, config_type: type):
    """Register a decode policy under ``name`` and for ``config_type``
    (reference: module_registry.py)."""
    def deco(cls):
        DECODE_POLICIES[name] = cls
        _CONFIG_TO_POLICY[config_type] = cls
        cls.arch = name
        return cls
    return deco


def policy_for(model_config) -> type:
    """Heuristic: map a model config to its decode policy (reference:
    heuristics.py:36). LlamaConfig covers llama/mistral/qwen2/phi3."""
    cls = _CONFIG_TO_POLICY.get(type(model_config))
    if cls is None:
        raise ValueError(
            f"no decode policy registered for {type(model_config).__name__}; "
            f"known: {sorted(DECODE_POLICIES)}")
    return cls


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _rope_tables(head_dim, max_seq_len, theta, yarn=None):
    """Rope tables as trace-local jnp constants: ``head_dim`` is the width
    that rotates (a partial rotary part passes its own), ``yarn`` a
    ``models.llama.YarnScaling`` or None. The numpy compute is cached in
    ``rope_freqs`` (identical ndarray objects across layers → XLA CSEs the
    constants); the jnp conversion must NOT be cached — a jnp array created
    under one jit trace is a tracer and may not leak into the next trace."""
    cos, sin = rope_freqs(head_dim, max_seq_len, theta, yarn)
    return jnp.asarray(cos), jnp.asarray(sin)


def _rope_rows(x, cos, sin, positions):
    """x: [N, H, d]; positions: [N] — rotary on per-row absolute positions,
    rotate-half over the leading ``2 * cos.shape[-1]`` dims; the dims past
    them (a partial rotary part) pass through."""
    rot = 2 * cos.shape[-1]
    cos_p = cos[positions][:, None, :]
    sin_p = sin[positions][:, None, :]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos_p - x2 * sin_p, x2 * cos_p + x1 * sin_p],
                          -1).astype(x.dtype)
    return out if rot == x.shape[-1] else \
        jnp.concatenate([out, x[..., rot:]], -1)


# ---------------------------------------------------------------------------
# Llama family (llama / mistral / qwen2 / phi3)
# ---------------------------------------------------------------------------
@register_policy("llama", LlamaConfig)
class LlamaPolicy:
    """reference: model_implementations/llama_v2 (+ mistral/qwen_v2/phi3 —
    LlamaConfig knobs: sliding_window, attention_bias, fused mappers)."""

    @staticmethod
    def cache_spec(cfg: LlamaConfig) -> KVCacheSpec:
        return KVCacheSpec(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_,
                           cfg.max_seq_len, cfg.dtype, cfg.sliding_window,
                           query_heads=cfg.num_heads)

    @staticmethod
    def _norm_scale(scale, cfg):
        # gemma stores norm weights as an offset from 1 (rms_scale_offset)
        return scale + 1.0 if cfg.rms_scale_offset else scale

    @staticmethod
    def embed(params, tokens, positions, cfg):
        x = params["model"]["embed"]["embedding"].astype(cfg.dtype)[tokens]
        if cfg.scale_embeddings:   # gemma normalizer
            x = x * jnp.sqrt(jnp.asarray(cfg.hidden_size,
                                         jnp.float32)).astype(x.dtype)
        return x

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params["model"][f"layer_{i}"]
        dtype = cfg.dtype
        ns = LlamaPolicy._norm_scale
        cos, sin = _rope_tables(cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta)
        with jax.named_scope("attn/qkv"):
            h = _rms(x, ns(lp["attn_norm"]["scale"], cfg), cfg.rms_norm_eps)
            q, k, v = _qkv(lp, h, dtype)
            q = _rope_rows(q, cos, sin, positions)
            k = _rope_rows(k, cos, sin, positions)
        attn = attend(q, k, v)
        with jax.named_scope("attn/out"):
            x = x + jnp.einsum("thk,hkd->td", attn,
                               lp["attn"]["wo"]["kernel"].astype(dtype))
        with jax.named_scope("mlp"):
            h2 = _rms(x, ns(lp["mlp_norm"]["scale"], cfg), cfg.rms_norm_eps)
            return x + _mlp(lp, h2, dtype, act=cfg.hidden_act), None

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, LlamaPolicy._norm_scale(
            params["model"]["final_norm"]["scale"], cfg), cfg.rms_norm_eps)
        if cfg.tie_embeddings:
            logits = x.astype(jnp.float32) @ \
                params["model"]["embed"]["embedding"].astype(jnp.float32).T
        else:
            logits = x.astype(jnp.float32) @ \
                params["model"]["lm_head"]["kernel"].astype(jnp.float32)
        from deepspeed_tpu.models.llama import softcap_logits
        return softcap_logits(logits, cfg.logits_soft_cap)


# ---------------------------------------------------------------------------
# Falcon (parallel attn+mlp, LayerNorm, MQA/GQA)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models.falcon import FalconConfig  # noqa: E402


@register_policy("falcon", FalconConfig)
class FalconPolicy:
    """reference: model_implementations/falcon."""

    @staticmethod
    def cache_spec(cfg: FalconConfig) -> KVCacheSpec:
        return KVCacheSpec(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_,
                           cfg.max_seq_len, cfg.dtype, None,
                           query_heads=cfg.num_heads)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["model"]["embed"]["embedding"].astype(cfg.dtype)[tokens]

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params["model"][f"layer_{i}"]
        dtype = cfg.dtype
        eps = cfg.layer_norm_eps
        if cfg.new_decoder_architecture:
            h = _layernorm(x, lp["ln_attn"]["scale"], lp["ln_attn"]["bias"], eps)
            h_mlp = _layernorm(x, lp["ln_mlp"]["scale"], lp["ln_mlp"]["bias"], eps)
        else:
            h = _layernorm(x, lp["input_ln"]["scale"], lp["input_ln"]["bias"], eps)
            h_mlp = h
        q = jnp.einsum("td,dhk->thk", h, lp["wq"]["kernel"].astype(dtype))
        k = jnp.einsum("td,dhk->thk", h, lp["wk"]["kernel"].astype(dtype))
        v = jnp.einsum("td,dhk->thk", h, lp["wv"]["kernel"].astype(dtype))
        cos, sin = _rope_tables(cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta)
        q = _rope_rows(q, cos, sin, positions)
        k = _rope_rows(k, cos, sin, positions)
        attn = attend(q, k, v)
        attn_out = jnp.einsum("thk,hkd->td", attn,
                              lp["wo"]["kernel"].astype(dtype))
        mlp = jax.nn.gelu(h_mlp @ lp["mlp_up"]["kernel"].astype(dtype))
        mlp_out = mlp @ lp["mlp_down"]["kernel"].astype(dtype)
        return x + attn_out + mlp_out, None  # parallel residual

    @staticmethod
    def unembed(params, x, cfg):
        m = params["model"]
        x = _layernorm(x, m["final_ln"]["scale"], m["final_ln"]["bias"],
                       cfg.layer_norm_eps)
        return x.astype(jnp.float32) @ \
            m["embed"]["embedding"].astype(jnp.float32).T   # tied


# ---------------------------------------------------------------------------
# OPT (learned positions, LayerNorm, ReLU MLP, biases everywhere, no rope)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models.opt import OPT_POSITION_OFFSET, OPTConfig  # noqa: E402


@register_policy("opt", OPTConfig)
class OPTPolicy:
    """reference: model_implementations/opt."""

    @staticmethod
    def cache_spec(cfg: OPTConfig) -> KVCacheSpec:
        return KVCacheSpec(cfg.num_layers, cfg.num_heads, cfg.head_dim_,
                           cfg.max_seq_len, cfg.dtype, None)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        m = params["model"]
        x = m["embed"]["embedding"].astype(cfg.dtype)[tokens]
        pos = m["pos_embed"][positions + OPT_POSITION_OFFSET].astype(cfg.dtype)
        return x + pos

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params["model"][f"layer_{i}"]
        dtype = cfg.dtype
        eps = cfg.layer_norm_eps
        h = _layernorm(x, lp["attn_ln"]["scale"], lp["attn_ln"]["bias"], eps)
        q = jnp.einsum("td,dhk->thk", h, lp["wq"]["kernel"].astype(dtype)) + \
            lp["wq"]["bias"].astype(dtype)
        k = jnp.einsum("td,dhk->thk", h, lp["wk"]["kernel"].astype(dtype)) + \
            lp["wk"]["bias"].astype(dtype)
        v = jnp.einsum("td,dhk->thk", h, lp["wv"]["kernel"].astype(dtype)) + \
            lp["wv"]["bias"].astype(dtype)
        attn = attend(q, k, v)               # no rope
        x = x + jnp.einsum("thk,hkd->td", attn,
                           lp["wo"]["kernel"].astype(dtype)) + \
            lp["wo"]["bias"].astype(dtype)
        h2 = _layernorm(x, lp["mlp_ln"]["scale"], lp["mlp_ln"]["bias"], eps)
        m = jax.nn.relu(h2 @ lp["fc1"]["kernel"].astype(dtype) +
                        lp["fc1"]["bias"].astype(dtype))
        return x + m @ lp["fc2"]["kernel"].astype(dtype) + \
            lp["fc2"]["bias"].astype(dtype), None

    @staticmethod
    def unembed(params, x, cfg):
        m = params["model"]
        x = _layernorm(x, m["final_ln"]["scale"], m["final_ln"]["bias"],
                       cfg.layer_norm_eps)
        return x.astype(jnp.float32) @ \
            m["embed"]["embedding"].astype(jnp.float32).T   # tied


def _expert_matmul_impl() -> str:
    """The grouped matmul under the served experts: the Pallas kernels on a
    TPU, ``jax.lax.ragged_dot`` elsewhere (as ``kv_cache._resolve_impl``
    resolves the attention kernels; ``kernel_interpret`` is the tests')."""
    return "kernel" if jax.default_backend() == "tpu" else "ragged_dot"


@functools.partial(jax.jit, static_argnames=("impl", "first"))
def _routed_sum(experts, h2, weights, ids, valid, impl, first=0):
    """``_chosen_experts`` by one ``impl``. A function of its own under
    ``jit`` so that a step program traces and lowers it once and not once a
    layer (its layers' shapes are the same)."""
    if impl == "ragged_dot":
        y, rows = grouped_expert_ffn(h2, experts, weights, ids, valid,
                                     first=first)
        visited = jnp.sum                # no tiles: every row fills its own
    else:
        from deepspeed_tpu.ops.pallas import grouped_matmul as gmm
        interpret = impl == "kernel_interpret"
        # a gated expert's first product is two stacks [E, D, F] in one pass,
        # an ungated one's (``w_in``) one stack [E, F, D]
        if "w_in" in experts:
            first_product, stacks = gmm.grouped_relu2_in, 1
            e, f, d = experts["w_in"].shape
        else:
            first_product, stacks = gmm.grouped_gate_up, 2
            e, d, f = experts["w_gate"].shape
        y, rows = grouped_expert_ffn(
            h2, experts, weights, ids, valid,
            matmul=functools.partial(gmm.grouped_matmul, interpret=interpret),
            gate_up=functools.partial(first_product, interpret=interpret),
            first=first)
        visited = functools.partial(
            gmm.visited_tile_rows, m=ids.size,
            tm=gmm.tiling(ids.size, e, d, f, h2.dtype, stacks)[0])
    # the layer's counts, in the order of ``STEP_COUNTER_ARGS``; the
    # assignments of rows that are no padding to experts held elsewhere are
    # what is left of the router's choices. Under the leaf that made ``rows``
    with jax.named_scope("moe/experts/sort"):
        tile_rows = visited(rows)
        absent = jnp.sum(valid) * ids.shape[1] - jnp.sum(rows)
        return y, jnp.stack([jnp.sum(rows), jnp.sum(rows > 0), tile_rows,
                             absent]).astype(jnp.int32)


def _chosen_experts(experts, h2, weights, ids, valid, first=0):
    """The routed sum of ``moe/grouped_experts.py`` (the chosen experts alone
    compute, bucket padding rows take none; on a TPU through the Pallas
    grouped matmuls, gate and up in one pass) and the layer's counts of
    ``STEP_COUNTER_ARGS``: two sums over the rows on each expert, which the
    router has anyway, the rows of the tiles the grouped matmul visited,
    from what the kernel is given, and the assignments left out because
    their expert is not among the stacked ones (``first``: these are the
    router's experts ``first ..``, one chip's share; 0 for a layer held
    whole). The callee's operations are lowered once, without their caller's
    names: what names them is inside it, the four leaves of ``moe/experts``
    (``telemetry/names.py`` ``SERVED_LEAF_SCOPES``)."""
    return _routed_sum(experts, h2, weights, ids, valid,
                       impl=_expert_matmul_impl(), first=first)


def _routed_assignments(cfg) -> int:
    """A policy's ``routed_assignments(cfg)``: the assignments to routed
    experts ONE row of a step program makes, top-k summed over the layers
    that route, whatever the row holds and wherever the experts are held. A
    step program's rows times it is the ``ids.size`` its expert layers sort,
    gather and gather back (``telemetry/names.py`` ``GATHERED_ROWS_ARG``),
    which the engine stamps beside the rows the grouped matmuls visited.
    Every policy whose blocks hand out counts states it; this is the form
    of the families whose config says ``is_dense(i)`` and
    ``num_experts_per_tok``."""
    return cfg.num_experts_per_tok * sum(
        not cfg.is_dense(i) for i in range(cfg.num_layers))


def _softmax_moe(moe, h2, cfg, valid):
    """Mixtral's and Qwen2-MoE's routed experts and their counts: softmax
    top-k in float32, then the chosen experts alone. Equivalent to the
    training dispatch when no token drops; no capacity here, so none does.

    One form at every row count since the grouped matmul is the Pallas
    kernel (chip runs at Mixtral's widths, PERF.md section 6, PR 34; one
    layer's routed sum, ms, the kernel / every row through every expert then
    pick, the form that step programs of up to 512 rows kept while XLA's
    grouped call multiplied whole 512-row tiles): 8 rows 3.42 / 3.79, 32
    rows 3.81 / 3.80, 64 rows 3.85 / 3.82, 128 rows 3.89 / 4.02, 256 rows
    3.96 / 4.53, 512 rows 4.73 / 8.53, 1,024 rows 6.74 / 16.98, 2,048 rows
    11.47 / 34.03. Both read every expert once at the memory roofline in a
    small step (within 1% of each other at 32 and 64 rows), and only the
    kernel leaves an expert nobody chose unread."""
    with jax.named_scope("moe/router"):
        weights, ids = softmax_route(h2, moe["gate"]["wg"]["kernel"],
                                     cfg.moe.top_k, cfg.moe.norm_topk_prob)
    return _chosen_experts(moe["experts"], h2, weights, ids, valid)


# ---------------------------------------------------------------------------
# Mixtral (llama attention + top-k MoE MLP)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models.mixtral import MixtralConfig  # noqa: E402


@register_policy("mixtral", MixtralConfig)
class MixtralPolicy:
    """reference: model_implementations/mixtral (+ qwen_v2_moe shape). The
    softmax router's top-k experts of a row compute and no other
    (``moe/grouped_experts.py``: rows sorted by expert, a grouped matmul a
    weight; ``_softmax_moe``). No token is dropped, and the step programs
    hand out how many rows the experts took, how many were touched and how
    many rows the grouped matmul's tiles held."""

    @staticmethod
    def cache_spec(cfg: MixtralConfig) -> KVCacheSpec:
        b = cfg.base
        return KVCacheSpec(b.num_layers, b.num_kv_heads, b.head_dim_,
                           b.max_seq_len, b.dtype, b.sliding_window,
                           query_heads=b.num_heads)

    @staticmethod
    def routed_assignments(cfg) -> int:
        return cfg.moe.top_k * cfg.base.num_layers      # every layer routes

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["embed"]["embedding"].astype(cfg.base.dtype)[tokens]

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        base = cfg.base
        dtype = base.dtype
        lp = params[f"layer_{i}"]
        cos, sin = _rope_tables(base.head_dim_, base.max_seq_len, base.rope_theta)
        with jax.named_scope("attn/qkv"):
            h = _rms(x, lp["attn_norm"]["scale"], base.rms_norm_eps)
            q, k, v = _qkv({"attn": lp["attn"]}, h, dtype)
            q = _rope_rows(q, cos, sin, positions)
            k = _rope_rows(k, cos, sin, positions)
        attn = attend(q, k, v)
        with jax.named_scope("attn/out"):
            x = x + jnp.einsum("thk,hkd->td", attn,
                               lp["attn"]["wo"]["kernel"].astype(dtype))
        h2 = _rms(x, lp["mlp_norm"]["scale"], base.rms_norm_eps)
        y, counts = _softmax_moe(lp["moe"], h2, cfg, valid)
        return x + y, counts

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, params["final_norm"]["scale"], cfg.base.rms_norm_eps)
        return x.astype(jnp.float32) @ \
            params["lm_head"]["kernel"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# BLOOM (ALiBi attention via head-dim augmentation, fused-qkv arch, LayerNorm)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models.bloom import (  # noqa: E402
    BloomConfig, alibi_augment, alibi_slopes)


@register_policy("bloom", BloomConfig)
class BloomPolicy:
    """reference: the BLOOM container + alibi softmax kernel
    (``module_inject/containers/bloom.py``,
    ``csrc/transformer/inference/csrc/softmax.cu`` alibi variant). ALiBi rides
    in one augmented head-dim column (``models/bloom.py:alibi_augment``), so
    the KV cache stores head_dim+2 and the paged kernel runs unchanged."""

    @staticmethod
    def cache_spec(cfg: BloomConfig) -> KVCacheSpec:
        return KVCacheSpec(cfg.num_layers, cfg.num_heads, cfg.head_dim_ + 2,
                           cfg.max_seq_len, cfg.dtype, None)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        m = params["model"]
        x = m["embed"]["embedding"].astype(cfg.dtype)[tokens]
        return _layernorm(x, m["embed_ln"]["scale"], m["embed_ln"]["bias"],
                          cfg.layer_norm_eps)

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params["model"][f"layer_{i}"]
        dtype = cfg.dtype
        eps = cfg.layer_norm_eps
        d = cfg.head_dim_
        h = _layernorm(x, lp["input_ln"]["scale"], lp["input_ln"]["bias"], eps)
        q = jnp.einsum("td,dhk->thk", h, lp["wq"]["kernel"].astype(dtype)) + \
            lp["wq"]["bias"].astype(dtype)
        k = jnp.einsum("td,dhk->thk", h, lp["wk"]["kernel"].astype(dtype)) + \
            lp["wk"]["bias"].astype(dtype)
        v = jnp.einsum("td,dhk->thk", h, lp["wv"]["kernel"].astype(dtype)) + \
            lp["wv"]["bias"].astype(dtype)
        slopes = jnp.asarray(alibi_slopes(cfg.num_heads))
        q, k, v = alibi_augment(q, k, v, slopes, positions)
        attn = attend(q, k, v)[..., :d]
        x = x + jnp.einsum("thk,hkd->td", attn,
                           lp["wo"]["kernel"].astype(dtype)) + \
            lp["wo"]["bias"].astype(dtype)
        h2 = _layernorm(x, lp["post_ln"]["scale"], lp["post_ln"]["bias"], eps)
        m = jax.nn.gelu(h2 @ lp["mlp_up"]["kernel"].astype(dtype) +
                        lp["mlp_up"]["bias"].astype(dtype))
        return x + m @ lp["mlp_down"]["kernel"].astype(dtype) + \
            lp["mlp_down"]["bias"].astype(dtype), None

    @staticmethod
    def unembed(params, x, cfg):
        m = params["model"]
        x = _layernorm(x, m["final_ln"]["scale"], m["final_ln"]["bias"],
                       cfg.layer_norm_eps)
        return x.astype(jnp.float32) @ \
            m["embed"]["embedding"].astype(jnp.float32).T   # tied


# ---------------------------------------------------------------------------
# GPT-NeoX / GPT-J (partial rotary, parallel residual, untied embed_out head)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models.gpt_neox import (  # noqa: E402
    GPTNeoXConfig, apply_partial_rotary)


@register_policy("gpt_neox", GPTNeoXConfig)
class GPTNeoXPolicy:
    """reference: gptneox/gptj containers (module_inject/containers)."""

    @staticmethod
    def cache_spec(cfg: GPTNeoXConfig) -> KVCacheSpec:
        return KVCacheSpec(cfg.num_layers, cfg.num_heads, cfg.head_dim_,
                           cfg.max_seq_len, cfg.dtype, None)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["model"]["embed"]["embedding"].astype(cfg.dtype)[tokens]

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params["model"][f"layer_{i}"]
        dtype = cfg.dtype
        eps = cfg.layer_norm_eps
        h = _layernorm(x, lp["input_ln"]["scale"], lp["input_ln"]["bias"], eps)
        q = jnp.einsum("td,dhk->thk", h, lp["wq"]["kernel"].astype(dtype)) + \
            lp["wq"]["bias"].astype(dtype)
        k = jnp.einsum("td,dhk->thk", h, lp["wk"]["kernel"].astype(dtype)) + \
            lp["wk"]["bias"].astype(dtype)
        v = jnp.einsum("td,dhk->thk", h, lp["wv"]["kernel"].astype(dtype)) + \
            lp["wv"]["bias"].astype(dtype)
        q = apply_partial_rotary(q, positions, cfg.rotary_dim_, cfg.rope_theta,
                                 cfg.max_seq_len)
        k = apply_partial_rotary(k, positions, cfg.rotary_dim_, cfg.rope_theta,
                                 cfg.max_seq_len)
        attn = attend(q, k, v)
        attn_out = jnp.einsum("thk,hkd->td", attn,
                              lp["wo"]["kernel"].astype(dtype)) + \
            lp["wo"]["bias"].astype(dtype)
        h2_src = x if cfg.parallel_residual else x + attn_out
        h2 = _layernorm(h2_src, lp["post_ln"]["scale"], lp["post_ln"]["bias"],
                        eps)
        m = jax.nn.gelu(h2 @ lp["mlp_up"]["kernel"].astype(dtype) +
                        lp["mlp_up"]["bias"].astype(dtype))
        mlp_out = m @ lp["mlp_down"]["kernel"].astype(dtype) + \
            lp["mlp_down"]["bias"].astype(dtype)
        return ((x + attn_out + mlp_out) if cfg.parallel_residual
                else h2_src + mlp_out), None

    @staticmethod
    def unembed(params, x, cfg):
        m = params["model"]
        x = _layernorm(x, m["final_ln"]["scale"], m["final_ln"]["bias"],
                       cfg.layer_norm_eps)
        return x.astype(jnp.float32) @ m["embed_out"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# GPT-2 (learned positions, pre-LN, tied wte head)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models.gpt2 import GPT2Config  # noqa: E402


@register_policy("gpt2", GPT2Config)
class GPT2Policy:
    """reference: HFGPT2LayerPolicy / megatron-gpt container."""

    @staticmethod
    def cache_spec(cfg: GPT2Config) -> KVCacheSpec:
        return KVCacheSpec(cfg.num_layers, cfg.num_heads, cfg.head_dim_,
                           cfg.max_seq_len, cfg.dtype, None)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        m = params["model"]
        return m["embed"]["embedding"].astype(cfg.dtype)[tokens] + \
            m["pos_embed"][positions].astype(cfg.dtype)

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params["model"][f"layer_{i}"]
        dtype = cfg.dtype
        eps = cfg.layer_norm_eps
        h = _layernorm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps)
        q = jnp.einsum("td,dhk->thk", h, lp["wq"]["kernel"].astype(dtype)) + \
            lp["wq"]["bias"].astype(dtype)
        k = jnp.einsum("td,dhk->thk", h, lp["wk"]["kernel"].astype(dtype)) + \
            lp["wk"]["bias"].astype(dtype)
        v = jnp.einsum("td,dhk->thk", h, lp["wv"]["kernel"].astype(dtype)) + \
            lp["wv"]["bias"].astype(dtype)
        attn = attend(q, k, v)               # no rope: positions are learned
        x = x + jnp.einsum("thk,hkd->td", attn,
                           lp["wo"]["kernel"].astype(dtype)) + \
            lp["wo"]["bias"].astype(dtype)
        h2 = _layernorm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps)
        m = jax.nn.gelu(h2 @ lp["mlp_up"]["kernel"].astype(dtype) +
                        lp["mlp_up"]["bias"].astype(dtype))
        return x + m @ lp["mlp_down"]["kernel"].astype(dtype) + \
            lp["mlp_down"]["bias"].astype(dtype), None

    @staticmethod
    def unembed(params, x, cfg):
        m = params["model"]
        x = _layernorm(x, m["final_ln"]["scale"], m["final_ln"]["bias"],
                       cfg.layer_norm_eps)
        return x.astype(jnp.float32) @ \
            m["embed"]["embedding"].astype(jnp.float32).T   # tied


# ---------------------------------------------------------------------------
# Qwen2-MoE (mixtral experts + gated shared expert, qwen2 attention bias)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models.qwen2_moe import Qwen2MoEConfig  # noqa: E402


@register_policy("qwen2_moe", Qwen2MoEConfig)
class Qwen2MoEPolicy:
    """reference: model_implementations/qwen_v2_moe — Mixtral serving (the
    chosen experts alone, counted) plus a dense shared expert whose output is
    scaled by a per-token sigmoid gate."""

    @staticmethod
    def cache_spec(cfg: Qwen2MoEConfig) -> KVCacheSpec:
        b = cfg.base
        return KVCacheSpec(b.num_layers, b.num_kv_heads, b.head_dim_,
                           b.max_seq_len, b.dtype, b.sliding_window,
                           query_heads=b.num_heads)

    @staticmethod
    def routed_assignments(cfg) -> int:
        return cfg.moe.top_k * cfg.base.num_layers      # every layer routes

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["embed"]["embedding"].astype(cfg.base.dtype)[tokens]

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        base = cfg.base
        dtype = base.dtype
        lp = params[f"layer_{i}"]
        cos, sin = _rope_tables(base.head_dim_, base.max_seq_len,
                                base.rope_theta)
        with jax.named_scope("attn/qkv"):
            h = _rms(x, lp["attn_norm"]["scale"], base.rms_norm_eps)
            q, k, v = _qkv({"attn": lp["attn"]}, h, dtype)
            q = _rope_rows(q, cos, sin, positions)
            k = _rope_rows(k, cos, sin, positions)
        attn = attend(q, k, v)
        with jax.named_scope("attn/out"):
            x = x + jnp.einsum("thk,hkd->td", attn,
                               lp["attn"]["wo"]["kernel"].astype(dtype))
        h2 = _rms(x, lp["mlp_norm"]["scale"], base.rms_norm_eps)
        moe_out, counts = _softmax_moe(lp["moe"], h2, cfg, valid)
        with jax.named_scope("mlp"):       # the dense shared expert
            se = lp["shared_expert"]
            g = jax.nn.silu(h2 @ se["w_gate"]["kernel"].astype(dtype))
            u = h2 @ se["w_up"]["kernel"].astype(dtype)
            shared = (g * u) @ se["w_down"]["kernel"].astype(dtype)
            gate = jax.nn.sigmoid(
                (h2 @ se["gate"]["kernel"].astype(dtype)).astype(jnp.float32))
            return x + moe_out + shared * gate.astype(dtype), counts

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, params["final_norm"]["scale"], cfg.base.rms_norm_eps)
        return x.astype(jnp.float32) @ \
            params["lm_head"]["kernel"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Gemma-2 (sandwich norms, logit softcaps, alternating sliding/full windows)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models.gemma2 import Gemma2Config  # noqa: E402


@register_policy("gemma2", Gemma2Config)
class Gemma2Policy:
    """models/gemma2.py's serving twin. The decoupled attention scale folds
    into q (kernel and gather both divide by sqrt(d)); the attention-logit
    softcap is applied in-kernel on the paged Pallas path
    (ops/pallas/paged_attention.py `softcap`) and mirrored by the gather
    fallback; cache_spec keeps the FULL window since odd layers attend
    globally."""

    @staticmethod
    def cache_spec(cfg: Gemma2Config) -> KVCacheSpec:
        return KVCacheSpec(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
                           cfg.max_seq_len, cfg.dtype, None,
                           query_heads=cfg.num_heads)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        x = params["embed"]["embedding"].astype(cfg.dtype)[tokens]
        return x * jnp.sqrt(jnp.asarray(cfg.hidden_size,
                                        jnp.float32)).astype(x.dtype)

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params[f"layer_{i}"]
        dtype = cfg.dtype
        eps = cfg.rms_norm_eps
        cos, sin = _rope_tables(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        h = _rms(x, lp["attn_norm"]["scale"] + 1.0, eps)
        q, k, v = _qkv(lp, h, dtype)
        q = _rope_rows(q, cos, sin, positions)
        k = _rope_rows(k, cos, sin, positions)
        # fold the decoupled scale: attend divides by sqrt(d), so prescale
        # by scale*sqrt(d) for a net query_pre_attn_scalar**-0.5
        q = q * jnp.asarray(cfg.query_pre_attn_scalar ** -0.5 *
                            np.sqrt(cfg.head_dim), dtype)
        attn = attend(q, k, v,
                      window=cfg.sliding_window if cfg.is_sliding(i) else None,
                      softcap=cfg.attn_logit_softcap)
        h = jnp.einsum("thk,hkd->td", attn,
                       lp["attn"]["wo"]["kernel"].astype(dtype))
        x = x + _rms(h, lp["post_attn_norm"]["scale"] + 1.0, eps)
        h2 = _rms(x, lp["pre_ffw_norm"]["scale"] + 1.0, eps)
        m = _mlp(lp, h2, dtype, act="gelu_tanh")
        return x + _rms(m, lp["post_ffw_norm"]["scale"] + 1.0, eps), None

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, params["final_norm"]["scale"] + 1.0, cfg.rms_norm_eps)
        from deepspeed_tpu.models.llama import softcap_logits
        logits = x.astype(jnp.float32) @ \
            params["embed"]["embedding"].astype(jnp.float32).T     # tied
        return softcap_logits(logits, cfg.final_logit_softcap)


# ---------------------------------------------------------------------------
# JoyAI-LLM-Flash (latent attention over one cached row a token, sigmoid
# routing with a correction bias through a grouped matmul, a shared expert,
# a leading dense layer)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models.joyai_llm_flash import (  # noqa: E402
    JoyAIFlashConfig, apply_rope_pairs, route)


def _latent_attention(lp, x, attend, positions, cfg):
    """A layer's latent attention of ``x`` [N, D], its own pre-norm inside:
    what the sublayer adds, the residual left to the caller."""
    ap = lp["attn"]
    dtype, eps = cfg.dtype, cfg.rms_norm_eps
    d_n, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    h = _rms(x, lp["attn_norm"]["scale"], eps)
    with jax.named_scope("attn/latent_q"):
        cq = _rms(h @ ap["wq_a"]["kernel"].astype(dtype),
                  ap["q_norm"]["scale"], eps)
        q = jnp.einsum("tr,rhk->thk", cq,
                       ap["wq_b"]["kernel"].astype(dtype))
        q_rope = apply_rope_pairs(q[..., d_n:], positions, cfg)
        ckv = h @ ap["wkv_a"]["kernel"].astype(dtype)
        row = jnp.concatenate(
            [_rms(ckv[:, :rank], ap["kv_norm"]["scale"], eps),
             apply_rope_pairs(ckv[:, rank:], positions, cfg)], -1)
    o = attend(q[..., :d_n], q_rope, row,
               ap["wkv_b"]["kernel"].astype(dtype), cfg.softmax_scale)
    with jax.named_scope("attn/out"):
        return jnp.einsum("thv,hvd->td", o, ap["wo"]["kernel"].astype(dtype))


def _dense_or_experts(lp, i, x, cfg, valid, route=route):
    """(what layer ``i``'s second sublayer adds to ``x`` [N, D], its own
    pre-norm inside, the layer's counts or None): the gated MLP of a dense
    layer, else the chosen routed experts of those held here (the router's
    ``first_expert ..``, by the family's ``route``) and the shared one where
    there is one."""
    dtype = cfg.dtype
    h2 = _rms(x, lp["mlp_norm"]["scale"], cfg.rms_norm_eps)
    if cfg.is_dense(i):
        with jax.named_scope("mlp"):
            return _mlp(lp, h2, dtype), None
    moe = lp["moe"]
    with jax.named_scope("moe/router"):
        weights, ids = route(h2, moe, cfg)
    y, counts = _chosen_experts(moe["experts"], h2, weights, ids, valid,
                                first=cfg.first_expert)
    if cfg.n_shared_experts:
        with jax.named_scope("moe/shared"):
            y = y + _mlp({"mlp": moe["shared"]}, h2, dtype)
    return y, counts


@register_policy("joyai_llm_flash", JoyAIFlashConfig)
class JoyAIFlashPolicy:
    """models/joyai_llm_flash.py's serving twin. The page row of a token is
    ``[RMS(c_kv) ; rope(k_r)]``; the latent page kind (``kv_cache``) reads it
    unfolded in a prefill chunk and folded in a decode batch. The chosen
    experts alone compute (``moe/grouped_experts.py``), and the step programs
    hand out how many rows they took and how many experts they touched."""

    @staticmethod
    def cache_spec(cfg: JoyAIFlashConfig) -> KVCacheSpec:
        return KVCacheSpec(cfg.num_layers, 1, cfg.latent_dim, cfg.max_seq_len,
                           cfg.dtype, None, latent_dim=cfg.latent_dim)

    routed_assignments = staticmethod(_routed_assignments)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["embed"]["embedding"].astype(cfg.dtype)[tokens]

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params[f"layer_{i}"]
        x = x + _latent_attention(lp, x, attend, positions, cfg)
        y, counts = _dense_or_experts(lp, i, x, cfg, valid)
        return x + y, counts

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return x.astype(jnp.float32) @ \
            params["lm_head"]["kernel"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Xing4.0 (JoyAI's sublayers with YaRN, several residual streams mixed round
# each by hyper-connections: the state between layers is [N, n, D])
# ---------------------------------------------------------------------------
from deepspeed_tpu.inference.v2 import hyper_connection as _hc  # noqa: E402
from deepspeed_tpu.models.xing4 import Xing4Config  # noqa: E402


@register_policy("xing4_0", Xing4Config)
class Xing4Policy(JoyAIFlashPolicy):
    """models/xing4.py's serving twin: JoyAI's cache, attention and experts
    (the same functions), each sublayer between ``hyper_connection.pre_mix``
    and ``post_mix``. The policy owns the residual: ``embed`` hands the loop
    ``hc_mult`` streams a row ([N, n, D]), which it passes from block to
    block unread, and ``unembed`` sums them."""

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return _hc.expand(JoyAIFlashPolicy.embed(params, tokens, positions,
                                                 cfg), cfg.hc)

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp, hc = params[f"layer_{i}"], cfg.hc
        u, mix = _hc.pre_mix(x, lp["hc_attn"], hc)
        x = _hc.post_mix(
            x, _latent_attention(lp, u, attend, positions, cfg), mix, hc)
        u, mix = _hc.pre_mix(x, lp["hc_mlp"], hc)
        y, counts = _dense_or_experts(lp, i, u, cfg, valid)
        return _hc.post_mix(x, y, mix, hc), counts

    @staticmethod
    def unembed(params, x, cfg):
        return JoyAIFlashPolicy.unembed(params, _hc.collapse(x), cfg)


# ---------------------------------------------------------------------------
# Laguna (full and sliding layers of different head counts over pages by
# layer kind, a per-head output gate, YaRN partial rope in full layers, a
# held share of softmax-routed experts, a shared expert, a leading dense
# layer)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models import laguna as _laguna  # noqa: E402


@register_policy("laguna", _laguna.LagunaConfig)
class LagunaPolicy:
    """models/laguna.py's serving twin. ``block`` decides heads, rope and
    window by the layer's index; the cache keeps the full layers' pages and
    the sliding layers' in a pool each (``cache_spec``'s ``layer_windows``),
    and the page kind attends a sliding layer over its window's blocks. The
    experts stacked here are the router's ``first_expert ..`` (one chip's
    share): the router keeps its width and top-k, what falls on an absent
    expert is left out and counted (``expert_rows_absent``)."""

    @staticmethod
    def cache_spec(cfg) -> KVCacheSpec:
        heads = dict(zip(cfg.layer_types, cfg.heads_per_layer))
        return KVCacheSpec(
            cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, cfg.max_seq_len,
            cfg.dtype, None,
            layer_windows=tuple(cfg.window(i) for i in range(cfg.num_layers)),
            query_heads={"full": heads.get(_laguna.FULL),
                         "window": heads.get(_laguna.SLIDING)})

    routed_assignments = staticmethod(_routed_assignments)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["embed"]["embedding"].astype(cfg.dtype)[tokens]

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params[f"layer_{i}"]
        ap = lp["attn"]
        dtype, eps = cfg.dtype, cfg.rms_norm_eps
        rope = cfg.rope(i)
        cos, sin = _rope_tables(
            int(cfg.head_dim * rope.partial_rotary_factor), cfg.max_seq_len,
            rope.theta, rope.yarn)
        with jax.named_scope("attn/qkv"):
            h = _rms(x, lp["attn_norm"]["scale"], eps)
            q, k, v = _qkv(lp, h, dtype)
            q = _rope_rows(q, cos, sin, positions)
            k = _rope_rows(k, cos, sin, positions)
        attn = attend(q, k, v)
        with jax.named_scope("attn/gate"):
            gate = jax.nn.sigmoid(
                (h @ ap["wg"]["kernel"].astype(dtype)).astype(jnp.float32))
            attn = attn * gate[..., None].astype(dtype)
        with jax.named_scope("attn/out"):
            x = x + jnp.einsum("thk,hkd->td", attn,
                               ap["wo"]["kernel"].astype(dtype))
        h2 = _rms(x, lp["mlp_norm"]["scale"], eps)
        if cfg.is_dense(i):
            with jax.named_scope("mlp"):
                return x + _mlp(lp, h2, dtype), None
        moe = lp["moe"]
        with jax.named_scope("moe/router"):
            weights, ids = _laguna.route(h2, moe, cfg)
        y, counts = _chosen_experts(moe["experts"], h2, weights, ids, valid,
                                    first=cfg.first_expert)
        with jax.named_scope("moe/shared"):
            y = y + _mlp({"mlp": moe["shared"]}, h2, dtype)
        return x + y, counts

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return x.astype(jnp.float32) @ \
            params["lm_head"]["kernel"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# MiMo-V2 (full and windowed layers with KV head counts of their own, keys
# wider than values, a sink logit a head in the windowed layers' softmax, a
# rope base a kind over a part of the head, scaled values, a held share of
# sigmoid-routed experts, a leading dense layer)
# ---------------------------------------------------------------------------
from deepspeed_tpu.inference.v2.kv_cache import HeadPageShape  # noqa: E402
from deepspeed_tpu.models import mimo_v2 as _mimo  # noqa: E402


@register_policy("mimo_v2", _mimo.MiMoV2Config)
class MiMoV2Policy:
    """models/mimo_v2.py's serving twin. ``block`` decides KV heads, rope
    base, sink and window by the layer's index; ``cache_spec`` states each
    kind's pages (``kind_pages``: 4 KV heads in a full layer, 8 in a windowed
    one, keys of 192 beside values of 128 at the published sizes), and the
    page kind attends a windowed layer over its window's blocks with the
    layer's sinks in the denominator. Values are cached scaled. The experts
    stacked here are the router's ``first_expert ..`` (``_dense_or_experts``,
    JoyAI's)."""

    @staticmethod
    def cache_spec(cfg) -> KVCacheSpec:
        kinds = {name: HeadPageShape(at.num_kv_heads, at.head_dim,
                                     at.v_head_dim)
                 for name, at in (("full", cfg.full), ("window", cfg.swa))}
        return KVCacheSpec(
            cfg.num_layers, cfg.full.num_kv_heads, cfg.full.head_dim,
            cfg.max_seq_len, cfg.dtype, None,
            layer_windows=tuple(cfg.window(i) for i in range(cfg.num_layers)),
            kind_pages=kinds,
            query_heads={"full": cfg.full.num_heads,
                         "window": cfg.swa.num_heads})

    routed_assignments = staticmethod(_routed_assignments)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["embed"]["embedding"].astype(cfg.dtype)[tokens]

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params[f"layer_{i}"]
        ap = lp["attn"]
        dtype = cfg.dtype
        cos, sin = _rope_tables(cfg.rotary_dim(i), cfg.max_seq_len,
                                cfg.rope_base(i))
        with jax.named_scope("attn/qkv"):
            h = _rms(x, lp["attn_norm"]["scale"], cfg.rms_norm_eps)
            q, k, v = _qkv(lp, h, dtype)
            q = _rope_rows(q, cos, sin, positions)
            k = _rope_rows(k, cos, sin, positions)
            v = v * jnp.asarray(cfg.attention_value_scale, dtype)
        how = {"sinks": ap["sink"]} if cfg.has_sink(i) else {}
        attn = attend(q, k, v, **how)
        with jax.named_scope("attn/out"):
            x = x + jnp.einsum("thv,hvd->td", attn,
                               ap["wo"]["kernel"].astype(dtype))
        y, counts = _dense_or_experts(lp, i, x, cfg, valid)
        return x + y, counts

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return x.astype(jnp.float32) @ \
            params["lm_head"]["kernel"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Granite 4.0-H (Mamba-2 state-space layers with an attention layer among
# every ten, a shared gated MLP, scalar multipliers, no positions)
# ---------------------------------------------------------------------------
from deepspeed_tpu.inference.v2.kv_cache import StateSlotShape  # noqa: E402
from deepspeed_tpu.models import granite_hybrid as _granite  # noqa: E402


def _mamba_mixer(mp, norm_scale, x, attend, cfg):
    """What a Mamba-2 layer adds to ``x`` [N, D], its own pre-norm inside:
    what needs no state is computed here (the first projection, the gated
    norm over ``cfg.norm_groups`` groups, the second projection) and the rest
    handed to ``attend`` as plain arrays (``kv_cache._StateSlots``)."""
    dtype, eps = cfg.dtype, cfg.rms_norm_eps
    with jax.named_scope("ssm/in_proj"):
        h = _rms(x, norm_scale, eps)
        z, xbc, dt = _granite.split_in_proj(
            h @ mp["in_proj"].astype(dtype), cfg)
        step = jax.nn.softplus(dt.astype(jnp.float32)
                               + mp["dt_bias"].astype(jnp.float32))
    y = attend(xbc, step, mp["conv_kernel"], mp["conv_bias"],
               mp["a_log"], mp["d"])
    with jax.named_scope("ssm/norm"):
        y = _granite.gated_norm(y, z, mp["norm"], eps, cfg.norm_groups)
    with jax.named_scope("ssm/out_proj"):
        return y @ mp["out_proj"].astype(dtype)


def _packed_heads(q, k, v, cfg, scale: float):
    """Heads narrower than the TPU's 128 lanes, ``cfg.kv_pack`` KV heads to a
    page's row (their keys side by side, and their values): (q [N, H, d *
    pack] with zeros in the other heads' lanes, so that a query head's score
    against the row is its own head's exactly, times ``scale`` over the
    pages' own ``(row width) ** -0.5``; k, v [N, H_kv / pack, d * pack];
    ``own`` [H, d * pack], a query head's lanes of its row)."""
    pack, d = cfg.kv_pack, cfg.head_dim
    q = q * jnp.asarray(scale * (d * pack) ** 0.5, q.dtype)
    # query head i reads KV head i // rep, which lies in lanes
    # ``lane .. lane + d`` of packed head i // (rep * pack)
    rep = cfg.num_heads // cfg.num_kv_heads
    lane = (jnp.arange(cfg.num_heads) // rep % pack) * d
    own = (jnp.arange(d * pack) // d * d)[None, :] \
        == lane[:, None]                                      # [H, d * pack]
    q = jnp.where(own, jnp.tile(q, (1, 1, pack)), 0)
    k, v = (t.reshape(t.shape[0], -1, d * pack) for t in (k, v))
    return q, k, v, own


def _own_lanes(attn, own, cfg):
    """A query head's output is its own head's lanes of the row's: [N, H,
    d * pack] -> [N, H, d]."""
    return jnp.sum(jnp.where(own, attn, 0).reshape(
        attn.shape[0], cfg.num_heads, cfg.kv_pack, cfg.head_dim), axis=2)


def _state_slot(cfg) -> StateSlotShape:
    """What a Mamba-2 layer of ``cfg`` keeps of a sequence."""
    return StateSlotShape(
        cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state,
        cfg.mamba_d_conv, cfg.conv_channels, cfg.mamba_chunk_size,
        cfg.mamba_groups)


@register_policy("granite_hybrid", _granite.GraniteHybridConfig)
class GraniteHybridPolicy:
    """models/granite_hybrid.py's serving twin. ``cache_spec`` names every
    layer's kind, ``full`` (an attention layer's pages) or ``state`` (a
    Mamba-2 layer's slot: ``StateSlotShape``), and the cache keeps both by
    layer kind. A Mamba layer's ``block`` computes what needs no state (the
    first projection, the gated norm, the second projection) and hands the
    rest to ``attend`` as plain arrays (the rows before the convolution, the
    step after softplus, the convolution's kernel and bias, ``a_log``, ``D``):
    the state kind runs the convolution behind the slot's tail and the
    recurrence from the slot's state, and writes both.

    An attention layer's heads are 64 wide, half a row of the TPU's 128
    lanes, which a page would pad: ``cfg.kv_pack`` KV heads share a row
    (their keys side by side, and their values), the cache sees
    ``num_kv_heads / kv_pack`` heads of 128, and a query head scores against
    the row with zeros in the other heads' lanes, which is its own head's
    score exactly; its output is its own head's lanes of the row's."""

    @staticmethod
    def cache_spec(cfg) -> KVCacheSpec:
        pack = cfg.kv_pack
        return KVCacheSpec(
            cfg.num_layers, cfg.num_kv_heads // pack, cfg.head_dim * pack,
            cfg.max_seq_len, cfg.dtype, None, query_heads=cfg.num_heads,
            layer_kinds=tuple("state" if cfg.is_mamba(i) else "full"
                              for i in range(cfg.num_layers)),
            state_slot=_state_slot(cfg))

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["embed"]["embedding"].astype(cfg.dtype)[tokens] \
            * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params[f"layer_{i}"]
        dtype, eps = cfg.dtype, cfg.rms_norm_eps
        r = jnp.asarray(cfg.residual_multiplier, dtype)
        if cfg.is_mamba(i):
            x = x + r * _mamba_mixer(lp["mamba"], lp["mixer_norm"]["scale"],
                                     x, attend, cfg)
        else:
            with jax.named_scope("attn/qkv"):
                h = _rms(x, lp["mixer_norm"]["scale"], eps)
                q, k, v, own = _packed_heads(*_qkv(lp, h, dtype), cfg,
                                             cfg.attention_multiplier)
            attn = attend(q, k, v)
            with jax.named_scope("attn/out"):
                x = x + r * jnp.einsum("thk,hkd->td",
                                       _own_lanes(attn, own, cfg),
                                       lp["attn"]["wo"]["kernel"].astype(dtype))
        with jax.named_scope("mlp"):
            h2 = _rms(x, lp["mlp_norm"]["scale"], eps)
            return x + r * _mlp(lp, h2, dtype), None

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return jnp.einsum("nd,vd->nv", x,
                          params["embed"]["embedding"].astype(x.dtype),
                          preferred_element_type=jnp.float32) \
            / cfg.logits_scaling


# ---------------------------------------------------------------------------
# Nemotron-H (every layer ONE mixer: a Mamba-2 layer of several groups of B
# and C, routed ungated relu2 experts beside a shared one, or attention
# without positions)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models import nemotron_h as _nemotron  # noqa: E402


@register_policy("nemotron_h", _nemotron.NemotronHConfig)
class NemotronHPolicy:
    """models/nemotron_h.py's serving twin: a layer is ``x + Mixer(RMSNorm(
    x))`` with the mixer its letter names, and nothing else (no MLP behind a
    mixer, so no ``mlp`` scope). ``cache_spec`` names every layer's kind:
    ``state`` (a Mamba-2 layer's slot, ``mamba_groups`` groups of B and C:
    ``_mamba_mixer``, granite's), ``full`` (an attention layer's pages, two
    KV heads of 128) or ``none`` (an expert layer keeps nothing of a
    sequence and calls no ``attend``). The experts stacked here are the
    router's ``first_expert ..`` (one chip's share), two matrices an expert:
    ``_chosen_experts`` takes a stack of ``w_in`` and ``w_down`` as ungated."""

    @staticmethod
    def cache_spec(cfg) -> KVCacheSpec:
        kinds = {_nemotron.MAMBA: "state", _nemotron.ATTENTION: "full",
                 _nemotron.EXPERTS: "none"}
        return KVCacheSpec(
            cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, cfg.max_seq_len,
            cfg.dtype, None, query_heads=cfg.num_heads,
            layer_kinds=tuple(kinds[letter] for letter in cfg.pattern),
            state_slot=_state_slot(cfg))

    @staticmethod
    def routed_assignments(cfg) -> int:
        return cfg.num_experts_per_tok * cfg.pattern.count(_nemotron.EXPERTS)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["embed"]["embedding"].astype(cfg.dtype)[tokens]

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params[f"layer_{i}"]
        dtype, kind = cfg.dtype, cfg.pattern[i]
        scale = lp["norm"]["scale"]
        if kind == _nemotron.MAMBA:
            return x + _mamba_mixer(lp["mamba"], scale, x, attend, cfg), None
        if kind == _nemotron.ATTENTION:
            with jax.named_scope("attn/qkv"):
                q, k, v = _qkv(lp, _rms(x, scale, cfg.rms_norm_eps), dtype)
            attn = attend(q, k, v)
            with jax.named_scope("attn/out"):
                return x + jnp.einsum(
                    "thk,hkd->td", attn,
                    lp["attn"]["wo"]["kernel"].astype(dtype)), None
        moe = lp["moe"]
        with jax.named_scope("moe/router"):
            u = _rms(x, scale, cfg.rms_norm_eps)
            weights, ids = route(u, moe, cfg)
        y, counts = _chosen_experts(moe["experts"], u, weights, ids, valid,
                                    first=cfg.first_expert)
        if cfg.n_shared_experts:
            with jax.named_scope("moe/shared"):
                shared = moe["shared"]
                up = u @ shared["w_up"]["kernel"].astype(dtype)
                y = y + relu2(up) @ shared["w_down"]["kernel"].astype(dtype)
        return x + y, counts

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return x.astype(jnp.float32) @ \
            params["lm_head"]["kernel"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# LFM2-MoE (gated short convolutions that keep a tail and no state, GQA with
# a norm a head on q and k, routed experts behind EVERY mixer)
# ---------------------------------------------------------------------------
from deepspeed_tpu.models import lfm2_moe as _lfm2  # noqa: E402


def _short_conv_mixer(cp, norm_scale, x, attend, cfg):
    """What a gated short-convolution layer adds to ``x`` [N, D], its own
    pre-norm inside: both projections and both gates are computed here (``B *
    v`` before the convolution, ``C *`` behind it) and the gated rows handed
    to ``attend`` with the taps (``kv_cache._TailSlots``: the taps' sum
    behind the slot's tail, no activation, the new tail written)."""
    dtype = cfg.dtype
    with jax.named_scope("conv/in_proj"):
        h = _rms(x, norm_scale, cfg.rms_norm_eps)
        b, c, v = jnp.split(h @ cp["in_proj"].astype(dtype), 3, axis=-1)
        gated = b * v
    conv = attend(gated, cp["conv_kernel"])
    with jax.named_scope("conv/out_proj"):
        return (c * conv) @ cp["out_proj"].astype(dtype)


@register_policy("lfm2_moe", _lfm2.Lfm2MoeConfig)
class Lfm2MoePolicy:
    """models/lfm2_moe.py's serving twin: a layer is a mixer, then a dense
    MLP or the chosen routed experts (``_dense_or_experts``, JoyAI's, behind
    the family's own ``route``), so an expert layer sits behind a state
    layer in ONE layer. ``cache_spec`` names every layer's kind: ``state`` (a
    conv layer, whose slot is the convolution's TAIL alone,
    ``StateSlotShape.tail_only``: 2 rows of ``hidden_size`` and no recurrent
    state, so the cache allocates no pool of states) or ``full`` (an
    attention layer's pages, two KV heads of 64 to a row: granite's
    ``kv_pack``). A conv layer's ``block`` computes both projections and both
    gates (``_short_conv_mixer``) and hands the kind the gated rows and the
    taps: the kind runs the three-tap sum behind the slot's tail, with no
    activation, and writes the new tail. An attention layer's q and k are
    RMS-normed a head before rope."""

    @staticmethod
    def cache_spec(cfg) -> KVCacheSpec:
        pack = cfg.kv_pack
        return KVCacheSpec(
            cfg.num_layers, cfg.num_kv_heads // pack, cfg.head_dim * pack,
            cfg.max_seq_len, cfg.dtype, None, query_heads=cfg.num_heads,
            layer_kinds=tuple("state" if cfg.is_conv(i) else "full"
                              for i in range(cfg.num_layers)),
            state_slot=StateSlotShape.tail_only(cfg.conv_width,
                                                cfg.hidden_size))

    routed_assignments = staticmethod(_routed_assignments)

    @staticmethod
    def embed(params, tokens, positions, cfg):
        return params["embed"]["embedding"].astype(cfg.dtype)[tokens]

    @staticmethod
    def block(params, i, x, attend, positions, cfg, valid):
        lp = params[f"layer_{i}"]
        dtype, eps = cfg.dtype, cfg.rms_norm_eps
        if cfg.is_conv(i):
            x = x + _short_conv_mixer(lp["conv"], lp["mixer_norm"]["scale"],
                                      x, attend, cfg)
        else:
            ap = lp["attn"]
            with jax.named_scope("attn/qkv"):
                q, k, v = _qkv(lp, _rms(x, lp["mixer_norm"]["scale"], eps),
                               dtype)
            with jax.named_scope("attn/qk_norm"):
                q = _lfm2.head_norm(q, ap["q_norm"], eps)
                k = _lfm2.head_norm(k, ap["k_norm"], eps)
            with jax.named_scope("attn/qkv"):
                cos, sin = _rope_tables(cfg.head_dim, cfg.max_seq_len,
                                        cfg.rope_theta)
                q, k, v, own = _packed_heads(
                    _rope_rows(q, cos, sin, positions),
                    _rope_rows(k, cos, sin, positions), v, cfg,
                    cfg.head_dim ** -0.5)
            attn = attend(q, k, v)
            with jax.named_scope("attn/out"):
                x = x + jnp.einsum("thk,hkd->td", _own_lanes(attn, own, cfg),
                                   ap["wo"]["kernel"].astype(dtype))
        y, counts = _dense_or_experts(lp, i, x, cfg, valid, _lfm2.route)
        return x + y, counts

    @staticmethod
    def unembed(params, x, cfg):
        x = _rms(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return jnp.einsum("nd,vd->nv", x,
                          params["embed"]["embedding"].astype(x.dtype),
                          preferred_element_type=jnp.float32)
