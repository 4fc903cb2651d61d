"""Plain reference for ``model_type: nemotron_h`` (NVIDIA-Nemotron-3-Nano-30B-
A3B): float32 ``jax.numpy`` at ``"highest"`` matmul precision, no kernel, no
cache, no batching, no chunks. It sees weights only through
``families/nemotron_h.py`` ``reference_weights`` and sizes only through the
published ``config.json`` keys. One layer is cast to float32 at a time.

``h_0 = E[token]``; layer ``l`` is ``h <- h + Mixer_l(RMSNorm_l(h))`` at
``layer_norm_epsilon``, ONE mixer a layer, chosen by letter ``l`` of
``hybrid_override_pattern``; ``logits = RMSNorm(h_L) W_head`` (untied). No
bias but the convolution's, no multiplier. With ``u = RMSNorm_l(h)``:

- ``M`` (Mamba-2; ``H = mamba_num_heads`` heads of ``P = mamba_head_dim``,
  ``N = ssm_state_size``, ``G = n_groups``): ``[z ; xBC ; dt] = u W_in``
  (widths ``H P``, ``H P + 2 G N``, ``H``); ``xBC_t <- silu(sum_{j < K}
  w_c[:, j] xBC_{t - K + 1 + j} + b_c)``, ``K = conv_kernel`` (rows before the
  first are 0); ``[x_t ; B_t ; C_t] = xBC_t`` with ``B_t``, ``C_t`` ``[G,
  N]``; head ``j`` reads group ``g = j // (H / G)``; ``D_t = softplus(dt_t +
  dt_bias)``, ``a_t = exp(-D_t exp(a_log))``,

      S_t = a_t S_{t-1} + D_t x_t (x) B_{t,g},  S_{-1} = 0,  y_t = S_t C_{t,g} + D x_t

  **token by token in a ``lax.scan``**: the recurrence itself, which shares
  nothing with the program's closed form over blocks or its kernel. ``y_t <-
  RMSNorm(y_t * silu(z_t))`` over EACH of the ``G`` groups' ``H P / G``
  values (the gate before the norm), times one learned scale of ``H P``;
  ``Mixer = y W_out``.
- ``E``: ``s = sigmoid(u W_r)`` over the router's ``published.
  n_routed_experts`` outputs; the ``num_experts_per_tok`` largest of ``s +
  e_score_correction_bias`` chosen (ties to the lower id), their ``s``
  renormalised to sum to 1, times ``routed_scaling_factor``; ``Mixer = sum_k
  w_k relu(u W_up,k)^2 W_down,k + relu(u W_sup)^2 W_sdown``: two matrices an
  expert, no gate (``mlp_hidden_act: relu2``), one shared expert.
- ``*``: ``q = u W_q`` (``num_attention_heads`` x ``head_dim``), ``k``, ``v``
  over ``num_key_value_heads``; query head ``i`` reads KV head ``i // (H /
  H_kv)``; no positional embedding; ``a_i = softmax(q_i k^T / sqrt(head_dim)
  + causal mask) v``; ``Mixer = concat(a) W_o``.

**A held share of the experts.** The weights hold ``n_routed_experts`` of
the router's ``published.n_routed_experts`` experts, from ``first_expert_held``
on: the router, its top-k and its renormalisation are over ALL its outputs,
and the part of the routed sum that falls on an expert not held is left out
(the other chip's to add), in the program and here alike.

Departures from the published description: none in the mathematics beyond
that share. What the published config leaves unstated is under ``assumed``
in the configuration file: no positional embedding in the attention layers
(``rope_theta`` and ``partial_rotary_factor`` are not read), ``expand`` not
read (the inner width is ``mamba_num_heads x mamba_head_dim``), no clamp on
the step beyond softplus, the gate before the grouped norm, a float32 state.
``chunk_size`` is a property of an implementation's closed form and is not
read here.

``logits`` returns its rows unbuilt (``Logits``): the harness slices the
generated positions off a sequence, and a row of 131,072 logits is 0.5 MB.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.granitemoehybrid import attention_mixer

F32 = jnp.float32
EXPERT_BLOCK = 4      # experts cast to float32 at a time
TOKEN_BLOCK = 1024    # tokens through a block of experts at a time
VOCAB_BLOCK = 16384   # columns of the head cast to float32 at a time
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


@functools.partial(jax.jit,
                   static_argnames=("eps", "heads", "state", "groups"))
def mamba_mixer(x, norm, w_in, conv_w, conv_b, dt_bias, a_log, d_skip,
                gate_norm, w_out, *, eps, heads, state, groups):
    """Mamba2(RMSNorm(x)): the module docstring's equations. x: [S, D];
    w_in: [D, 2 H P + 2 G N + H]; conv_w: [H P + 2 G N, K]; dt_bias, a_log,
    d_skip: [H]; gate_norm: [H P]; w_out: [H P, D]."""
    u = _rms_norm(x, norm, eps)
    proj = u @ w_in.astype(F32)
    channels, taps = conv_w.shape
    inner = channels - 2 * groups * state
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + channels],
                  proj[:, inner + channels:])
    s = x.shape[0]
    # the causal depthwise convolution: tap j meets the row K - 1 - j back
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(conv_b.astype(F32) + sum(
        padded[j:j + s] * conv_w.astype(F32)[:, j] for j in range(taps)))
    xs = xbc[:, :inner].reshape(s, heads, inner // heads)
    bc = xbc[:, inner:].reshape(s, 2, groups, state)
    # each head's own group's B and C: [S, H, N]
    b_rows, c_rows = (jnp.repeat(bc[:, i], heads // groups, axis=1)
                      for i in (0, 1))
    step = jax.nn.softplus(dt + dt_bias.astype(F32))          # [S, H]
    decay = jnp.exp(-step * jnp.exp(a_log.astype(F32)))       # [S, H]

    def token(carry, row):
        x_t, b_t, c_t, step_t, decay_t = row
        carry = decay_t[:, None, None] * carry \
            + (step_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return carry, jnp.sum(carry * c_t[:, None, :], axis=-1)
    _, y = jax.lax.scan(token, jnp.zeros((heads, inner // heads, state), F32),
                        (xs, b_rows, c_rows, step, decay))
    y = (y + d_skip.astype(F32)[None, :, None] * xs).reshape(s, inner)
    # the gate, then each group's values normalised alone
    y = (y * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return (y.reshape(s, inner) * gate_norm.astype(F32)) @ w_out.astype(F32)


@jax.jit
def router_scores(u, router):
    return jax.nn.sigmoid(u @ router.astype(F32))


def routing_weights(scores, bias, *, top_k: int, scaling: float):
    """[S, E] combine weights from sigmoid ``scores`` [S, E]: the ``top_k``
    largest of ``scores + bias`` chosen (ties to the lower id), their scores
    renormalised to sum to 1, times ``scaling``; 0 elsewhere."""
    s = scores.shape[0]
    idx = jnp.argsort(-(scores + bias.astype(F32)), axis=-1,
                      stable=True)[:, :top_k]
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], idx].set(
        top * scaling)


@jax.jit
def _expert_block(u, combine, w_in, down):
    """sum_e combine[:, e] * relu(u W_up,e)^2 W_down,e over one block of
    stacked experts, ``W_up,e = w_in[e].T`` (both stacks are [E, F, D]: a
    hidden unit's row in and its row out)."""
    act = relu2(jnp.einsum("sd,efd->esf", u, w_in.astype(F32)))
    out = jnp.einsum("esf,efd->esd", act, down.astype(F32))
    return jnp.einsum("se,esd->sd", combine, out)


@jax.jit
def shared_expert(u, up, down):
    return relu2(u @ up.astype(F32)) @ down.astype(F32)


def expert_mixer(x, layer: dict, hf: dict):
    """Experts(RMSNorm(x)): the held experts' part of the routed sum (the
    module docstring's held share) and the shared expert."""
    u = _rms_norm(x, layer["norm"], _eps(hf))
    combine = routing_weights(
        router_scores(u, layer["router"]), layer["router_bias"],
        top_k=int(hf["num_experts_per_tok"]),
        scaling=float(hf.get("routed_scaling_factor") or 1.0))
    ex = layer["experts"]
    first = int(hf.get("first_expert_held", 0))
    held = ex["in"].shape[0]
    combine = combine[:, first:first + held]
    parts = []
    for t in range(0, u.shape[0], TOKEN_BLOCK):
        rows = slice(t, t + TOKEN_BLOCK)
        y = jnp.zeros_like(u[rows])
        for e in range(0, held, EXPERT_BLOCK):
            block = slice(e, e + EXPERT_BLOCK)
            y = y + _expert_block(u[rows], combine[rows, block],
                                  ex["in"][block], ex["down"][block])
        if "shared" in layer:
            y = y + shared_expert(u[rows], layer["shared"]["up"],
                                  layer["shared"]["down"])
        parts.append(y)
    return jnp.concatenate(parts, axis=0)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, norm, head, *, eps):
    """[rows, V] = RMSNorm(x) W_head, the head cast to float32
    ``VOCAB_BLOCK`` columns at a time."""
    h = _rms_norm(x, norm, eps)
    return jnp.concatenate(
        [h @ head[:, v:v + VOCAB_BLOCK].astype(F32)
         for v in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)


def _eps(hf: dict) -> float:
    return float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)))


def hidden_states(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, D] float32 states after the last layer, before the final norm."""
    eps = _eps(hf)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for kind, layer in zip(hf["hybrid_override_pattern"],
                               weights["layers"]):
            if kind == MAMBA:
                x = x + mamba_mixer(
                    x, layer["norm"], layer["in_proj"], layer["conv_w"],
                    layer["conv_b"], layer["dt_bias"], layer["a_log"],
                    layer["d"], layer["gate_norm"], layer["out_proj"],
                    eps=eps, heads=int(hf["mamba_num_heads"]),
                    state=int(hf["ssm_state_size"]),
                    groups=int(hf.get("n_groups", 1)))
            elif kind == ATTENTION:
                x = x + attention_mixer(
                    x, layer["norm"], layer["q"], layer["k"], layer["v"],
                    layer["o"], eps=eps,
                    scale=float(layer["q"].shape[-1]) ** -0.5)
            else:
                x = x + expert_mixer(x, layer, hf)
    return x


class Logits:
    """The [S, V] float32 logits of one sequence, a row built when it is
    asked for."""

    def __init__(self, states, norm, head, eps):
        self.states, self.norm, self.head, self.eps = states, norm, head, eps
        self.shape = (states.shape[0], head.shape[1])

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(self.states[rows], self.norm, self.head,
                               eps=self.eps)

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self[:], dtype)


def logits(weights: dict, hf: dict, tokens) -> Logits:
    """[S, V] float32 logits of one sequence: the full forward pass."""
    return Logits(hidden_states(weights, hf, tokens), weights["final_norm"],
                  weights["head"], _eps(hf))


def loss(weights: dict, hf: dict, rows) -> float:
    """Mean next-token cross-entropy over rows of equal length."""
    losses = []
    for row in rows:
        row = jnp.asarray(row, jnp.int32)
        logp = jax.nn.log_softmax(logits(weights, hf, row)[:-1], axis=-1)
        losses.append(float(-jnp.mean(
            jnp.take_along_axis(logp, row[1:, None], axis=-1))))
    return float(np.mean(losses))
