"""Plain reference for ``model_type: lfm2_moe`` (LFM2-24B-A2B): float32
``jax.numpy`` at ``"highest"`` matmul precision, no kernel, no cache, no
batching, no chunks. It sees weights only through ``families/lfm2_moe.py``
``reference_weights`` and sizes only through the published ``config.json``
keys, and shares no function with the program. One layer is cast to float32
at a time.

``D = hidden_size``, layer ``l`` of kind ``layer_types[l]``, RMSNorm ``x *
rsqrt(mean(x^2) + norm_eps) * w``, no bias anywhere:

- ``h_0 = E[token]``. A layer: ``h += M(RMSNorm_op(h))``, then ``h +=
  F(RMSNorm_ffn(h))``. ``logits = RMSNorm(h_L) E^T`` (the head is the
  embedding: ``assumed`` in the configuration file).
- ``conv``: ``[B ; C ; v] = u W_in`` (``D`` each), ``g_t = B_t * v_t``, ``c_t
  = w[:, 0] g_{t-2} + w[:, 1] g_{t-1} + w[:, 2] g_t`` for ``conv_L_cache`` 3
  (rows before the first are 0; **the three-term sum written out**, no bias,
  no activation), ``M = (C_t * c_t) W_out``.
- ``full_attention``: ``q = u W_q`` (``num_attention_heads`` x ``head_dim`` =
  ``hidden_size / num_attention_heads``), ``k``, ``v`` over
  ``num_key_value_heads``; q and k RMS-normed over each head's values, then
  rope over the whole head (rotate-half: dims ``j`` and ``j + d/2`` a pair,
  angle ``t theta^{-2j/d}``); query head ``i`` reads KV head ``i // (H /
  H_kv)``; ``a_i = softmax(q_i k^T head_dim^-0.5 + causal mask) v``; ``M =
  concat(a) W_o``.
- ``F``, ``l < num_dense_layers``: ``(silu(y W_1) * y W_3) W_2``. Otherwise
  ``s = sigmoid(y W_g)`` (``num_experts``), the ``num_experts_per_tok``
  largest of ``s + expert_bias`` (ties to the lower id), weights ``s /
  (sum of the chosen s + 1e-6) * routed_scaling_factor``, and **every token
  through its chosen experts**, computed as every expert over every token
  times a combine weight that is 0 where the expert was not chosen.

``logits`` returns its rows unbuilt (``Logits``): the harness slices the
generated positions off a sequence.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512         # queries scored at a time: [heads, 512, S] floats
TOKEN_BLOCK = 1024    # tokens through an MLP or a block of experts at a time
EXPERT_BLOCK = 4      # experts cast to float32 at a time
VOCAB_BLOCK = 16384   # rows of the embedding cast to float32 at a time
CONV = "conv"
ROUTE_EPS = 1e-6


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta: float):
    """x: [S, H, d], row t at position t. Rotate-half."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]   # [S, d/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps",))
def conv_mixer(x, norm, w_in, taps, w_out, *, eps):
    """ShortConv(RMSNorm(x)). x: [S, D]; w_in: [D, 3 D]; taps: [D, 3]; w_out:
    [D, D]."""
    if taps.shape[1] != 3:
        raise ValueError(f"conv_L_cache {taps.shape[1]}: the reference "
                         f"writes out the sum of three taps")
    u = _rms_norm(x, norm, eps)
    proj = u @ w_in.astype(F32)
    d = x.shape[1]
    b, c, v = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    g = b * v
    w = taps.astype(F32)
    back1 = jnp.pad(g, ((1, 0), (0, 0)))[:-1]                 # g_{t-1}
    back2 = jnp.pad(g, ((2, 0), (0, 0)))[:-2]                 # g_{t-2}
    conv = w[:, 0] * back2 + w[:, 1] * back1 + w[:, 2] * g
    return (c * conv) @ w_out.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def attention_mixer(x, norm, w_q, w_k, w_v, w_o, q_norm, k_norm, *, eps,
                    theta):
    """Attention(RMSNorm(x)). x: [S, D]; w_q: [D, H, d]; w_k, w_v: [D, H_kv,
    d]; w_o: [H, d, D]; q_norm, k_norm: [d]."""
    h = _rms_norm(x, norm, eps)
    q = jnp.einsum("sd,dhk->shk", h, w_q.astype(F32))
    k = jnp.einsum("sd,dhk->shk", h, w_k.astype(F32))
    v = jnp.einsum("sd,dhk->shk", h, w_v.astype(F32))
    q = _rope(_rms_norm(q, q_norm, eps), theta)
    k = _rope(_rms_norm(k, k_norm, eps), theta)
    s, heads, d = q.shape
    rep = heads // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)[None, :]
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        qpos = start + jnp.arange(qb.shape[0])[:, None]
        scores = jnp.einsum("qhk,shk->hqs", qb, k) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where((kpos <= qpos)[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqs,shk->qhk", probs, v))
    return jnp.einsum("shk,hkd->sd", jnp.concatenate(outs, axis=0),
                      w_o.astype(F32))


@jax.jit
def gated_mlp(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1.astype(F32)) * (u @ w3.astype(F32))) \
        @ w2.astype(F32)


@jax.jit
def router_scores(u, router):
    return jax.nn.sigmoid(u @ router.astype(F32))


@functools.partial(jax.jit, static_argnames=("top_k", "scaling"))
def routing_weights(scores, bias, *, top_k: int, scaling: float):
    """[S, E] combine weights from sigmoid ``scores`` [S, E]: the ``top_k``
    largest of ``scores + bias`` chosen (ties to the lower id), their scores
    over their sum plus ``ROUTE_EPS``, times ``scaling``; 0 elsewhere."""
    s = scores.shape[0]
    idx = jnp.argsort(-(scores + bias.astype(F32)), axis=-1,
                      stable=True)[:, :top_k]
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + ROUTE_EPS)
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], idx].set(
        top * scaling)


@jax.jit
def _expert_block(y, u, combine, w1, w3, w2, first):
    """y + sum_e combine[:, e] * (silu(u W1_e) * u W3_e) W2_e over the
    ``EXPERT_BLOCK`` experts from ``first`` on of a layer's stacks (w1, w3:
    [E, D, F]; w2: [E, F, D]; combine: [S, E]): that block alone is cast to
    float32. ``first`` is a value, so one program serves every block."""
    def cut(w, axis=0):
        return jax.lax.dynamic_slice_in_dim(w, first, EXPERT_BLOCK, axis)
    w1, w3, w2 = (cut(w).astype(F32) for w in (w1, w3, w2))
    act = jax.nn.silu(jnp.einsum("sd,edf->esf", u, w1)) \
        * jnp.einsum("sd,edf->esf", u, w3)
    return y + jnp.einsum("se,esd->sd", cut(combine, 1),
                          jnp.einsum("esf,efd->esd", act, w2))


def feed_forward(x, layer: dict, hf: dict):
    """F(RMSNorm_ffn(x)): the dense MLP where the layer has one, else the
    routed experts."""
    u = _rms_norm(x, layer["ffn_norm"], _eps(hf))
    parts = []
    if "w1" in layer:
        for t in range(0, u.shape[0], TOKEN_BLOCK):
            parts.append(gated_mlp(u[t:t + TOKEN_BLOCK], layer["w1"],
                                   layer["w3"], layer["w2"]))
        return jnp.concatenate(parts, axis=0)
    combine = routing_weights(
        router_scores(u, layer["router"]), layer["expert_bias"],
        top_k=int(hf["num_experts_per_tok"]),
        scaling=float(hf.get("routed_scaling_factor") or 1.0))
    ex = layer["experts"]
    for t in range(0, u.shape[0], TOKEN_BLOCK):
        ut, ct = u[t:t + TOKEN_BLOCK], combine[t:t + TOKEN_BLOCK]
        y = jnp.zeros_like(ut)
        for e in range(0, ex["w1"].shape[0], EXPERT_BLOCK):
            y = _expert_block(y, ut, ct, ex["w1"], ex["w3"], ex["w2"], e)
        parts.append(y)
    return jnp.concatenate(parts, axis=0)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, norm, embedding, *, eps):
    """[rows, V] = RMSNorm(x) E^T, the embedding cast to float32
    ``VOCAB_BLOCK`` rows at a time."""
    h = _rms_norm(x, norm, eps)
    return jnp.concatenate(
        [h @ embedding[v:v + VOCAB_BLOCK].astype(F32).T
         for v in range(0, embedding.shape[0], VOCAB_BLOCK)], axis=-1)


def _eps(hf: dict) -> float:
    return float(hf.get("norm_eps", 1e-5))


def hidden_states(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, D] float32 states after the last layer, before the final norm."""
    eps = _eps(hf)
    theta = float(hf["rope_parameters"]["rope_theta"])
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for kind, layer in zip(hf["layer_types"], weights["layers"]):
            if kind == CONV:
                x = x + conv_mixer(x, layer["operator_norm"], layer["in_proj"],
                                   layer["taps"], layer["out_proj"], eps=eps)
            else:
                x = x + attention_mixer(
                    x, layer["operator_norm"], layer["q"], layer["k"],
                    layer["v"], layer["o"], layer["q_norm"], layer["k_norm"],
                    eps=eps, theta=theta)
            x = x + feed_forward(x, layer, hf)
    return x


class Logits:
    """The [S, V] float32 logits of one sequence, a row built when it is
    asked for."""

    def __init__(self, states, norm, embedding, eps):
        self.states, self.norm, self.embedding = states, norm, embedding
        self.eps = eps
        self.shape = (states.shape[0], embedding.shape[0])

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(self.states[rows], self.norm, self.embedding,
                               eps=self.eps)

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self[:], dtype)


def logits(weights: dict, hf: dict, tokens) -> Logits:
    """[S, V] float32 logits of one sequence: the full forward pass."""
    return Logits(hidden_states(weights, hf, tokens), weights["final_norm"],
                  weights["embed"], _eps(hf))


def loss(weights: dict, hf: dict, rows) -> float:
    """Mean next-token cross-entropy over rows of equal length."""
    losses = []
    for row in rows:
        row = jnp.asarray(row, jnp.int32)
        logp = jax.nn.log_softmax(logits(weights, hf, row)[:-1], axis=-1)
        losses.append(float(-jnp.mean(
            jnp.take_along_axis(logp, row[1:, None], axis=-1))))
    return float(np.mean(losses))
