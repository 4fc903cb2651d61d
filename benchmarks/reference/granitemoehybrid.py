"""Plain reference for ``model_type: granitemoehybrid`` (Granite 4.0-H:
granite-4.0-h-micro): float32 ``jax.numpy`` at ``"highest"`` matmul precision,
no kernel, no cache, no batching, no chunks. It sees weights only through
``families/granitemoehybrid.py`` ``reference_weights`` and sizes only through
the published ``config.json`` keys. One layer is cast to float32 at a time.

``d = hidden_size``, layer ``l`` of kind ``layer_types[l]``, ``r =
residual_multiplier``, RMSNorm at ``rms_norm_eps``, no bias but the
convolution's:

- ``h_0 = embedding_multiplier E[token]``. A layer: ``h += r M(RMSNorm(h))``,
  then ``h += r W_out(silu(g) * v)`` with ``[g ; v] = W_in RMSNorm(h)``
  (``shared_intermediate_size`` each).
- ``attention``: ``q = u W_q`` (``num_attention_heads`` x ``head_dim`` =
  ``hidden_size / num_attention_heads``), ``k``, ``v`` over
  ``num_key_value_heads``; query head ``i`` reads KV head ``i // (H / H_kv)``;
  no positional embedding (``position_embedding_type: "nope"``); ``a_i =
  softmax(attention_multiplier q_i k^T + causal mask) v``; ``M = concat(a)
  W_o``.
- ``mamba`` (Mamba-2; ``H = mamba_n_heads`` heads of ``P = mamba_d_head``,
  ``N = mamba_d_state``, one group): ``[z ; xBC ; dt] = u W_in`` (widths
  ``H P``, ``H P + 2 N``, ``H``); ``xBC_t <- silu(sum_{j < K} w_c[:, j]
  xBC_{t - K + 1 + j} + b_c)`` with ``K = mamba_d_conv`` (rows before the
  first are 0); ``[x_t ; B_t ; C_t] = xBC_t``; a head's step ``D_t =
  softplus(dt_t + dt_bias)`` and decay ``a_t = exp(-D_t exp(a_log))``;

      S_t = a_t S_{t-1} + D_t x_t (x) B_t,   S_{-1} = 0,   y_t = S_t C_t + D x_t

  **token by token in a ``lax.scan``**: this is the recurrence itself, and
  shares nothing with the program's closed form over blocks or its kernel.
  ``y_t <- RMSNorm(y_t * silu(z_t))`` over all ``H P`` values (the gate
  before the norm, one norm group); ``M = y W_o``.
- ``logits = RMSNorm(h_L) E^T / logits_scaling`` (``tie_word_embeddings``).

Departures from the published description: none in the mathematics. What the
published config leaves unstated is under ``assumed`` in the configuration
file: no clamp on the step beyond softplus (``time_step_limit`` at its
default, (0, inf)), the gate before the norm, one norm group, a float32
state. ``mamba_chunk_size`` is a property of an implementation's closed form
and is not read here.

``logits`` returns its rows unbuilt (``Logits``): the harness slices the
generated positions off a sequence, and a row of 100,352 logits is 0.4 MB.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512         # queries scored at a time: [heads, 512, S] floats
TOKEN_BLOCK = 2048    # tokens through the MLP at a time
VOCAB_BLOCK = 16384   # rows of the embedding cast to float32 at a time
MAMBA = "mamba"


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def attention_mixer(x, norm, w_q, w_k, w_v, w_o, *, eps, scale):
    """Attention(RMSNorm(x)). x: [S, D]; w_q: [D, H, d]; w_k, w_v: [D, H_kv,
    d]; w_o: [H, d, D]. Causal, no positions, scores times ``scale``."""
    h = _rms_norm(x, norm, eps)
    q = jnp.einsum("sd,dhk->shk", h, w_q.astype(F32))
    k = jnp.einsum("sd,dhk->shk", h, w_k.astype(F32))
    v = jnp.einsum("sd,dhk->shk", h, w_v.astype(F32))
    s, heads, _ = q.shape
    rep = heads // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)[None, :]
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        qpos = start + jnp.arange(qb.shape[0])[:, None]
        scores = jnp.einsum("qhk,shk->hqs", qb, k) * scale
        probs = jax.nn.softmax(
            jnp.where((kpos <= qpos)[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqs,shk->qhk", probs, v))
    return jnp.einsum("shk,hkd->sd", jnp.concatenate(outs, axis=0),
                      w_o.astype(F32))


@functools.partial(jax.jit, static_argnames=("eps", "heads", "state"))
def mamba_mixer(x, norm, w_in, conv_w, conv_b, dt_bias, a_log, d_skip,
                gate_norm, w_out, *, eps, heads, state):
    """Mamba2(RMSNorm(x)): the module docstring's equations. x: [S, D];
    w_in: [D, 2 H P + 2 N + H]; conv_w: [H P + 2 N, K]; conv_b: [H P + 2 N];
    dt_bias, a_log, d_skip: [H]; gate_norm: [H P]; w_out: [H P, D]."""
    u = _rms_norm(x, norm, eps)
    proj = u @ w_in.astype(F32)
    channels, taps = conv_w.shape
    inner = channels - 2 * state
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + channels],
                  proj[:, inner + channels:])
    s = x.shape[0]
    # the causal depthwise convolution: tap j meets the row K - 1 - j back
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(conv_b.astype(F32) + sum(
        padded[j:j + s] * conv_w.astype(F32)[:, j] for j in range(taps)))
    xs = xbc[:, :inner].reshape(s, heads, inner // heads)
    b_rows, c_rows = xbc[:, inner:inner + state], xbc[:, inner + state:]
    step = jax.nn.softplus(dt + dt_bias.astype(F32))          # [S, H]
    decay = jnp.exp(-step * jnp.exp(a_log.astype(F32)))       # [S, H]

    def token(carry, row):
        x_t, b_t, c_t, step_t, decay_t = row
        carry = decay_t[:, None, None] * carry \
            + (step_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return carry, jnp.sum(carry * c_t[None, None, :], axis=-1)
    _, y = jax.lax.scan(token, jnp.zeros((heads, inner // heads, state), F32),
                        (xs, b_rows, c_rows, step, decay))
    y = (y + d_skip.astype(F32)[None, :, None] * xs).reshape(s, inner)
    y = _rms_norm(y * jax.nn.silu(z), gate_norm, eps)
    return y @ w_out.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _gated_mlp_rows(x, norm, w_gate, w_up, w_down, *, eps):
    h = _rms_norm(x, norm, eps)
    return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def gated_mlp(x, norm, w_gate, w_up, w_down, *, eps):
    """``W_down(silu(W_gate h) * W_up h)`` of ``h = RMSNorm(x)``,
    ``TOKEN_BLOCK`` rows at a time."""
    return jnp.concatenate(
        [_gated_mlp_rows(x[t:t + TOKEN_BLOCK], norm, w_gate, w_up, w_down,
                         eps=eps) for t in range(0, x.shape[0], TOKEN_BLOCK)],
        axis=0)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, norm, embedding, scaling, *, eps):
    """[rows, V] = RMSNorm(x) E^T / scaling, the embedding cast to float32
    ``VOCAB_BLOCK`` rows at a time."""
    h = _rms_norm(x, norm, eps)
    return jnp.concatenate(
        [h @ embedding[v:v + VOCAB_BLOCK].astype(F32).T
         for v in range(0, embedding.shape[0], VOCAB_BLOCK)], axis=-1) \
        / scaling


def hidden_states(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, D] float32 states after the last layer, before the final norm."""
    eps = float(hf.get("rms_norm_eps", 1e-5))
    r = float(hf.get("residual_multiplier", 1.0))
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32) \
            * float(hf.get("embedding_multiplier", 1.0))
        for kind, layer in zip(hf["layer_types"], weights["layers"]):
            if kind == MAMBA:
                mixed = mamba_mixer(
                    x, layer["mixer_norm"], layer["in_proj"], layer["conv_w"],
                    layer["conv_b"], layer["dt_bias"], layer["a_log"],
                    layer["d"], layer["gate_norm"], layer["out_proj"],
                    eps=eps, heads=int(hf["mamba_n_heads"]),
                    state=int(hf["mamba_d_state"]))
            else:
                mixed = attention_mixer(
                    x, layer["mixer_norm"], layer["q"], layer["k"],
                    layer["v"], layer["o"], eps=eps,
                    scale=float(hf["attention_multiplier"]))
            x = x + r * mixed
            x = x + r * gated_mlp(x, layer["mlp_norm"], layer["gate"],
                                  layer["up"], layer["down"], eps=eps)
    return x


class Logits:
    """The [S, V] float32 logits of one sequence, a row built when it is
    asked for."""

    def __init__(self, states, norm, embedding, scaling, eps):
        self.states, self.norm, self.embedding = states, norm, embedding
        self.scaling, self.eps = scaling, eps
        self.shape = (states.shape[0], embedding.shape[0])

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(self.states[rows], self.norm, self.embedding,
                               self.scaling, eps=self.eps)

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self[:], dtype)


def logits(weights: dict, hf: dict, tokens) -> Logits:
    """[S, V] float32 logits of one sequence: the full forward pass."""
    return Logits(hidden_states(weights, hf, tokens), weights["final_norm"],
                  weights["embed"], float(hf.get("logits_scaling", 1.0)),
                  float(hf.get("rms_norm_eps", 1e-5)))


def loss(weights: dict, hf: dict, rows) -> float:
    """Mean next-token cross-entropy over rows of equal length."""
    losses = []
    for row in rows:
        row = jnp.asarray(row, jnp.int32)
        logp = jax.nn.log_softmax(logits(weights, hf, row)[:-1], axis=-1)
        losses.append(float(-jnp.mean(
            jnp.take_along_axis(logp, row[1:, None], axis=-1))))
    return float(np.mean(losses))
