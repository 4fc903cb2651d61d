"""Plain float32 decoder blocks, written from the published descriptions
(Mistral 7B, arXiv:2310.06825; Mixtral of Experts, arXiv:2401.04088) in
straightforward ``jax.numpy``: no kernel, no cache, no batching. Independent
of the program: it sees weights only through a family's ``reference_weights``
view (``families/<model_type>.py``) and sizes only through the published
``config.json`` keys.

Every matmul runs at ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul is otherwise computed in bf16 passes. One layer (for a routed
MLP one expert) is cast to float32 at a time, so the reference fits beside the
weights the program serves or trains.

Departures from the published models: none in the mathematics. Rotary
embedding uses the half-split layout of the Hugging Face implementation
(pairs (i, i + d/2)), which is what checkpoints in that format assume.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 1024      # queries scored at a time: [heads, 1024, S] floats


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x: [S, H, d] at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = np.outer(np.arange(s, dtype=np.float64), inv)
    cos = jnp.asarray(np.cos(angles), F32)[:, None, :]
    sin = jnp.asarray(np.sin(angles), F32)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("theta", "window", "eps"))
def attention_block(x, norm, wq, wk, wv, wo, *, theta, window, eps):
    """x + Attention(RMSNorm(x)). x: [S, D]; wq: [D, H, d]; wk, wv:
    [D, Hkv, d]; wo: [H, d, D]. Causal; with ``window`` query t sees keys
    (t - window, t]."""
    s = x.shape[0]
    h = _rms_norm(x, norm, eps)
    q = _rope(jnp.einsum("sd,dhk->shk", h, wq.astype(F32)), theta)
    k = _rope(jnp.einsum("sd,dhk->shk", h, wk.astype(F32)), theta)
    v = jnp.einsum("sd,dhk->shk", h, wv.astype(F32))
    heads, kv_heads, d = q.shape[1], k.shape[1], q.shape[2]
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    kpos = jnp.arange(s)[None, :]
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        qpos = start + jnp.arange(qb.shape[0])[:, None]
        keep = kpos <= qpos
        if window is not None:
            keep &= kpos > qpos - window
        scores = jnp.einsum("qhk,shk->hqs", qb, k) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqs,shk->qhk", probs, v))
    attn = jnp.concatenate(outs, axis=0)
    return x + jnp.einsum("shk,hkd->sd", attn, wo.astype(F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def mlp_input(x, norm, *, eps):
    return _rms_norm(x, norm, eps)


@jax.jit
def gated_mlp(h, w_gate, w_up, w_down):
    """down(silu(gate(h)) * up(h)): the SwiGLU MLP of both families; for
    Mixtral, one expert."""
    g = h @ w_gate.astype(F32)
    u = h @ w_up.astype(F32)
    return (jax.nn.silu(g) * u) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("top_k",))
def routing_weights(h, router, *, top_k):
    """[S, E] combine weights: softmax over all experts, keep the top_k,
    renormalise the kept ones to sum to 1 (Mixtral, section 2.1)."""
    probs = jax.nn.softmax(h @ router.astype(F32), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, norm, head, *, eps):
    return _rms_norm(x, norm, eps) @ head.astype(F32)


@jax.jit
def next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def hidden_states(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, D] float32 states after the last layer, before the final norm."""
    eps = float(hf.get("rms_norm_eps", 1e-5))
    theta = float(hf.get("rope_theta", 10000.0))
    window = hf.get("sliding_window")
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for layer in weights["layers"]:
            x = attention_block(x, layer["attn_norm"], layer["q"], layer["k"],
                                layer["v"], layer["o"], theta=theta,
                                window=window, eps=eps)
            h = mlp_input(x, layer["mlp_norm"], eps=eps)
            if "router" in layer:
                combine = routing_weights(h, layer["router"],
                                          top_k=int(hf["num_experts_per_tok"]))
                ex = layer["experts"]
                for e in range(ex["gate"].shape[0]):
                    x = x + combine[:, e:e + 1] * gated_mlp(
                        h, ex["gate"][e], ex["up"][e], ex["down"][e])
            else:
                x = x + gated_mlp(h, layer["gate"], layer["up"], layer["down"])
    return x


def logits(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, V] float32 logits of one sequence: the full forward pass."""
    x = hidden_states(weights, hf, tokens)
    with jax.default_matmul_precision("highest"):
        return head_logits(x, weights["final_norm"], weights["head"],
                           eps=float(hf.get("rms_norm_eps", 1e-5)))


def loss(weights: dict, hf: dict, rows) -> float:
    """Mean next-token cross-entropy over rows of equal length."""
    losses = [float(next_token_loss(logits(weights, hf, row),
                                    jnp.asarray(row, jnp.int32)))
              for row in rows]
    return float(np.mean(losses))
