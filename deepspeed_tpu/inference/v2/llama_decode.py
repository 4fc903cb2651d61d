"""Cache-aware Llama forward for serving: chunked prefill + batched paged decode.

Reference analog: the inference v2 kernel pipeline (``linear_blocked_kv_rotary``,
``blocked_flash``, ``logits_gather`` in ``inference/v2/kernels/ragged_ops/``) and
the per-arch model implementations (``inference/v2/model_implementations/llama_v2``).

Attention runs through the Pallas paged kernel on TPU (block tables in scalar
prefetch — pages stream from the paged pool with no context re-materialization,
``ops/pallas/paged_attention.py``); elsewhere the gather-based reference path
with identical semantics runs (``attn_impl`` static arg: auto|kernel|
kernel_interpret|gather).

TPU redesign: pure functions over the *training* model's param pytree
(``LlamaForCausalLM`` — same weights serve and train, no module surgery), with
static bucketed shapes so each (bucket, batch) pair compiles once:

- ``prefill_chunk``: one sequence, a [bucket]-padded token chunk; writes K/V into
  its cache blocks, runs flash attention against the gathered context, returns the
  last real token's logits (SplitFuse chunks: q_offset = chunk start).
- ``decode_step``: a [B]-padded batch of sequences, one token each; scatter-writes
  K/V, attends over gathered paged context.

Padding tokens write into a reserved trash block (the pool's last block), so no
masking is needed on the write path. Causal masking doubles as padding masking on
the read path: gathered positions >= context length can never satisfy
qpos >= kpos.
"""

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import LlamaConfig
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_attention_pool, paged_attention_reference)

ATTN_IMPLS = ("auto", "kernel", "kernel_interpret", "gather")


def _resolve_impl(attn_impl: str) -> str:
    """``auto`` to the Pallas kernel on a TPU and the gather path elsewhere."""
    if attn_impl not in ATTN_IMPLS:
        raise ValueError(f"unknown attn_impl {attn_impl!r}; one of {ATTN_IMPLS}")
    if attn_impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "gather"
    return attn_impl


def _paged_attn(q, cache_data, layer, block_tables, start_pos, window,
                attn_impl: str, softcap=None, scales=None):
    """q: [B, T, H, d]; dispatch kernel vs gather reference over the head-major
    cache [L, 2, Hkv, NB, bs, d]. ``softcap`` (gemma2) is supported by both
    the kernel and the gather path; ``scales`` ([L, 2, Hkv, NB] fp32, fp8
    pages) dequantizes per (head, page) on load in both paths."""
    impl = _resolve_impl(attn_impl)
    if impl == "gather":
        ks, vs = (scales[layer, 0], scales[layer, 1]) if scales is not None \
            else (None, None)
        return paged_attention_reference(q, cache_data[layer, 0],
                                         cache_data[layer, 1], block_tables,
                                         start_pos, window=window,
                                         softcap=softcap, k_scales=ks,
                                         v_scales=vs)
    # the kernel takes the pool whole: a slice of it is a copy of it
    return paged_attention_pool(q, cache_data, layer, block_tables, start_pos,
                                window=window, softcap=softcap, scales=scales,
                                interpret=impl == "kernel_interpret")


def _latent_paged_attn(q_nope, q_rope, pool, layer, block_tables, positions,
                       w_ukv, scale, attn_impl: str):
    """Decode over a latent pool [L, NB, bs, W], one token a sequence, with
    the up-projections folded: ``q~_i = W_uk_i^T q_nope_i`` scores against
    the cached rows themselves and ``W_uv_i`` is applied to the summed rows
    (``ops/pallas/latent_attention.py``). q_nope: [B, H, d_n]; q_rope:
    [B, H, d_r], rotated; w_ukv: [rank, H, d_n + d_v]. Returns [B, H, d_v].
    The same dispatch as ``_paged_attn``, kernel against gather path."""
    from deepspeed_tpu.ops.pallas.latent_attention import (
        latent_paged_attention, latent_paged_attention_reference)
    rank, d_n = w_ukv.shape[0], q_nope.shape[-1]
    with jax.named_scope("attn/latent_q"):
        q = jnp.concatenate(
            [jnp.einsum("bhk,rhk->bhr", q_nope, w_ukv[..., :d_n]), q_rope], -1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[-1] - q.shape[-1])))
    impl = _resolve_impl(attn_impl)
    with jax.named_scope("attn/latent_paged"):
        if impl == "gather":
            o = latent_paged_attention_reference(
                q, pool[layer], block_tables, positions, scale, rank)
        else:
            o = latent_paged_attention(
                q, pool, layer, block_tables, positions, scale, rank,
                interpret=impl == "kernel_interpret")
        return jnp.einsum("bhr,rhv->bhv", o, w_ukv[..., d_n:])


def _latent_prefill_attn(q_nope, q_rope, pool, layer, block_table, start,
                         w_ukv, scale, attn_impl: str):
    """One sequence's chunk over a latent pool, unfolded: the context's rows
    (the chunk's own, already written, among them) are gathered from the
    pages and every head's keys and values up-projected from them, 2 x (192 +
    128) operations a pair a head at the published sizes where the folded
    form costs 2 x (576 + 512). q_nope: [T, H, d_n]; q_rope: [T, H, d_r],
    rotated; block_table: [MB]; start: the chunk's first position. Returns
    [T, H, d_v]."""
    from deepspeed_tpu.ops.pallas.latent_attention import (
        PREFILL_BLOCK_K, latent_prefill_attention,
        latent_prefill_attention_reference)
    rank, d_n = w_ukv.shape[0], q_nope.shape[-1]
    d_r = q_rope.shape[-1]
    nb, bs = pool.shape[1], pool.shape[2]
    # whole key blocks for the kernel: dead slots read the trash page, which
    # no query's horizon reaches
    mb = block_table.shape[0]
    keys = mb * bs
    if keys > PREFILL_BLOCK_K:
        keys = -(-keys // PREFILL_BLOCK_K) * PREFILL_BLOCK_K
    table = jnp.pad(block_table, (0, -(-keys // bs) - mb),
                    constant_values=nb - 1)
    rows = pool[layer, table].reshape(-1, pool.shape[-1])[:keys]
    # keys and values each from their own half of the up-projection: one
    # product sliced afterwards is two more copies of the context
    ckv = rows[:, :rank]
    args = (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
            jnp.einsum("sr,rhk->hsk", ckv, w_ukv[..., :d_n]),
            rows[:, rank:rank + d_r],
            jnp.einsum("sr,rhk->hsk", ckv, w_ukv[..., d_n:]), start, scale)
    impl = _resolve_impl(attn_impl)
    if impl == "gather":
        out = latent_prefill_attention_reference(*args)
    else:
        out = latent_prefill_attention(*args,
                                       interpret=impl == "kernel_interpret")
    return out.transpose(1, 0, 2)


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _qkv(lp, x, dtype):
    """x: [T, D] -> q [T,H,d], k/v [T,Hkv,d] via DenseGeneral kernels (+ bias
    when present — qwen2-style attention_bias)."""
    q = jnp.einsum("td,dhk->thk", x, lp["attn"]["wq"]["kernel"].astype(dtype))
    k = jnp.einsum("td,dhk->thk", x, lp["attn"]["wk"]["kernel"].astype(dtype))
    v = jnp.einsum("td,dhk->thk", x, lp["attn"]["wv"]["kernel"].astype(dtype))
    if "bias" in lp["attn"]["wq"]:
        q = q + lp["attn"]["wq"]["bias"].astype(dtype)
        k = k + lp["attn"]["wk"]["bias"].astype(dtype)
        v = v + lp["attn"]["wv"]["bias"].astype(dtype)
    return q, k, v


def _mlp(lp, x, dtype, act: str = "silu"):
    g = x @ lp["mlp"]["w_gate"]["kernel"].astype(dtype)
    u = x @ lp["mlp"]["w_up"]["kernel"].astype(dtype)
    if act == "silu":
        gated = jax.nn.silu(g)
    elif act == "gelu_tanh":
        gated = jax.nn.gelu(g, approximate=True)
    else:
        raise ValueError(f"unsupported hidden_act {act!r} (silu | gelu_tanh)")
    return (gated * u) @ lp["mlp"]["w_down"]["kernel"].astype(dtype)


def prefill_chunk(params, cache_data, tokens, start, block_table, true_len,
                  cfg: LlamaConfig, block_size: int, attn_impl: str = "auto"):
    """One sequence, one chunk. tokens: [Tb] (bucket-padded); start: chunk offset;
    block_table: [MB] block ids (trash-padded); true_len: real chunk tokens.
    Returns (last-token logits [V], updated cache_data).

    Thin llama-specialized wrapper over the arch-generic loop
    (``generic_decode.prefill_chunk_g`` + ``modules.LlamaPolicy``)."""
    from deepspeed_tpu.inference.v2.generic_decode import prefill_chunk_g
    from deepspeed_tpu.inference.v2.modules import LlamaPolicy
    return prefill_chunk_g(params, cache_data, tokens, start, block_table,
                           true_len, policy=LlamaPolicy, cfg=cfg,
                           block_size=block_size, attn_impl=attn_impl)[:2]


def decode_step(params, cache_data, tokens, positions, block_tables, valid,
                cfg: LlamaConfig, block_size: int, attn_impl: str = "auto"):
    """Batched single-token decode. tokens/positions/valid: [B];
    block_tables: [B, MB]. Returns (logits [B, V], updated cache_data).

    Thin llama-specialized wrapper over the arch-generic loop."""
    from deepspeed_tpu.inference.v2.generic_decode import decode_step_g
    from deepspeed_tpu.inference.v2.modules import LlamaPolicy
    return decode_step_g(params, cache_data, tokens, positions, block_tables,
                         valid, policy=LlamaPolicy, cfg=cfg,
                         block_size=block_size, attn_impl=attn_impl)[:2]
