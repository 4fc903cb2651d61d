"""Plain reference for ``model_type: qwen2_moe`` (Qwen1.5-MoE, "Qwen1.5-MoE:
Matching 7B Model Performance with 1/3 Activated Parameters", and Hugging
Face's ``Qwen2MoeSparseMoeBlock``): the pre-norm decoder of ``decoder.py``
with a bias on the query, key and value projections, and in place of the MLP
``num_experts`` SwiGLU experts of width ``moe_intermediate_size``, of which a
linear router picks ``num_experts_per_tok`` per token and mixes them by their
softmax weights as they are (renormalised only where ``norm_topk_prob``
says so), plus one shared SwiGLU expert of width
``shared_expert_intermediate_size`` that every token passes, scaled per token
by the sigmoid of a linear gate.

Plain float32 ``jax.numpy`` at ``"highest"`` matmul precision, on
``decoder.py``'s blocks (norm, rotary embedding, SwiGLU, head, loss). The
attention is written out here because ``decoder.attention_block`` has no
bias and a family is added as files alone, with no edit to a file that is
there. Test data: ``benchmark_rehearsal.py`` drops this file into
``reference/`` of a temporary copy.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.decoder import (F32, Q_BLOCK, _rms_norm, _rope,
                                          gated_mlp, head_logits, mlp_input,
                                          next_token_loss)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def biased_attention_block(x, norm, wq, wk, wv, wo, bq, bk, bv, *, theta, eps):
    """x + Attention(RMSNorm(x)), causal, with q, k, v = h W + b. x: [S, D];
    wq: [D, H, d]; wk, wv: [D, Hkv, d]; wo: [H, d, D]; biases [H, d]."""
    s = x.shape[0]
    h = _rms_norm(x, norm, eps)
    q = jnp.einsum("sd,dhk->shk", h, wq.astype(F32)) + bq.astype(F32)
    k = jnp.einsum("sd,dhk->shk", h, wk.astype(F32)) + bk.astype(F32)
    v = jnp.einsum("sd,dhk->shk", h, wv.astype(F32)) + bv.astype(F32)
    q, k = _rope(q, theta), _rope(k, theta)
    heads, kv_heads, d = q.shape[1], k.shape[1], q.shape[2]
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    kpos = jnp.arange(s)[None, :]
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        keep = kpos <= start + jnp.arange(qb.shape[0])[:, None]
        scores = jnp.einsum("qhk,shk->hqs", qb, k) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqs,shk->qhk", probs, v))
    attn = jnp.concatenate(outs, axis=0)
    return x + jnp.einsum("shk,hkd->sd", attn, wo.astype(F32))


@functools.partial(jax.jit, static_argnames=("top_k", "renormalise"))
def routing_weights(h, router, *, top_k, renormalise):
    """[S, E] combine weights: softmax over all experts, the top_k kept at
    their own value (or renormalised to sum to 1), the others 0."""
    probs = jax.nn.softmax(h @ router.astype(F32), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    if renormalise:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)


@jax.jit
def shared_expert(h, shared):
    out = gated_mlp(h, shared["gate"], shared["up"], shared["down"])
    return jax.nn.sigmoid(h @ shared["sigmoid_gate"].astype(F32)) * out


def logits(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, V] float32 logits of one sequence: the full forward pass."""
    eps = float(hf.get("rms_norm_eps", 1e-6))
    theta = float(hf.get("rope_theta", 10000.0))
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for layer in weights["layers"]:
            x = biased_attention_block(
                x, layer["attn_norm"], layer["q"], layer["k"], layer["v"],
                layer["o"], layer["q_bias"], layer["k_bias"], layer["v_bias"],
                theta=theta, eps=eps)
            h = mlp_input(x, layer["mlp_norm"], eps=eps)
            combine = routing_weights(
                h, layer["router"], top_k=int(hf["num_experts_per_tok"]),
                renormalise=bool(hf.get("norm_topk_prob", False)))
            x = x + shared_expert(h, layer["shared"])
            ex = layer["experts"]
            for e in range(ex["gate"].shape[0]):
                x = x + combine[:, e:e + 1] * gated_mlp(
                    h, ex["gate"][e], ex["up"][e], ex["down"][e])
        return head_logits(x, weights["final_norm"], weights["head"], eps=eps)


def loss(weights: dict, hf: dict, rows) -> float:
    """Mean next-token cross-entropy over rows of equal length."""
    return float(np.mean([float(next_token_loss(
        logits(weights, hf, row), jnp.asarray(row, jnp.int32)))
        for row in rows]))
