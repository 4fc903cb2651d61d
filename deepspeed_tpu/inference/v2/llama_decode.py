"""The Llama family's layer pieces as pure functions over the *training*
model's param pytree (``LlamaForCausalLM`` — same weights serve and train, no
module surgery): ``_rms``, ``_qkv``, ``_mlp``.

Reference analog: the per-arch model implementations
(``inference/v2/model_implementations/llama_v2``). The decode policies
(``modules.py``) and the layer-streaming engine (``zero_inference.py``) build
their blocks from these; the served loop is ``generic_decode.py`` and what a
KV page is, with the attention over it, ``kv_cache.py``.
"""

import jax
import jax.numpy as jnp


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
