"""Plain reference for ``model_type: laguna`` (Laguna-S-2.1): float32
``jax.numpy`` at ``"highest"`` matmul precision, no kernel, no cache, no
batching. It sees weights only through ``families/laguna.py``
``reference_weights`` and sizes only through the published ``config.json``
keys (and the configuration file's ``first_expert_held``). One layer's
attention (a KV head's group of query heads at a time), and a block of
``EXPERT_BLOCK`` experts, is cast to float32 at a time.

Layer ``l`` of kind ``layer_types[l]`` with ``H_l =
num_attention_heads_per_layer[l]`` query heads over ``num_key_value_heads`` KV
heads of ``head_dim`` d, ``h = RMSNorm(x)`` (eps ``rms_norm_eps``), no bias:

- ``q = h W_q`` (``H_l`` x d), ``k = h W_k``, ``v = h W_v``; query head ``i``
  reads KV head ``i // (H_l / num_key_value_heads)``.
- rope, rotate-half form (dim ``j`` pairs with ``j + r/2`` over the rotary
  part ``r = partial_rotary_factor * d``, the other dims pass through), with
  ``rope_parameters`` of the layer's kind. ``default``: ``inv_freq_j =
  theta ** (-2j / r)``. ``yarn``: with ``dim(n) = r ln(original / (2 pi n)) /
  (2 ln theta)``, ``low = floor(dim(beta_fast))``, ``high =
  ceil(dim(beta_slow))`` (clamped to 0 .. r - 1), ``ramp_j = clip((j - low) /
  (high - low), 0, 1)``: ``inv_freq_j = ramp_j * theta ** (-2j / r) / factor +
  (1 - ramp_j) * theta ** (-2j / r)``, and ``cos``, ``sin`` times
  ``attention_factor``.
- ``a_i = softmax(q_i k^T / sqrt(d) + mask) v``, causal; in a sliding layer
  key ``j`` is seen by query ``t`` only if ``t - sliding_window < j <= t``.
- ``g = sigmoid(h W_g)`` (``W_g``: hidden x ``H_l``); ``a_i <- g_i a_i``;
  ``x <- x + concat(a) W_o``.
- ``h2 = RMSNorm(x)``. A layer in ``mlp_only_layers``: ``x <- x +
  W_down(silu(W_gate h2) * W_up h2)``. Every other: ``p = softmax(h2 W_r)``
  over the router's whole width; the ``num_experts_per_tok`` largest, ties to
  the lower id; ``w = moe_routed_scaling_factor * p_sel / sum(p_sel)``;
  ``x <- x + sum_k w_k E_k(h2) + S(h2)``, ``E`` and ``S`` gated-silu MLPs.
- head: RMSNorm, the untied ``lm_head``.

**The held share.** The weights' stack of experts may be a share of the
router's: experts ``first_expert_held .. first_expert_held + E - 1`` of the
``W_r.shape[1]`` the router scores. The sum then runs over the chosen experts
that are held (the absent chip adds the rest), the shared expert whole: what
guide section 4 asks the reference of a share cut to compute. With every
expert held it is the model.

Departures from the published model, each under ``assumed`` in the
configuration file: the router scores by softmax, the shared expert is added
ungated, the gate is the sigmoid of a linear map of the layer's normed input
applied before ``W_o``, no q/k norm, rotate-half pairing.

``logits`` returns its rows unbuilt (``Logits``): the harness slices the
generated positions off a sequence, and a row of 100,352 logits is 0.4 MB.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 128         # queries scored at a time: [heads, 128, S] floats
EXPERT_BLOCK = 8      # experts cast to float32 at a time
TOKEN_BLOCK = 2048    # tokens through a block of experts at a time
VOCAB_BLOCK = 16384   # columns of the head cast to float32 at a time
SLIDING = "sliding_attention"


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope_table(params: dict, head_dim: int, positions: int):
    """(cos, sin) float64 [positions, r / 2] of one layer kind's
    ``rope_parameters``: the module docstring's formulas."""
    r = int(head_dim * params.get("partial_rotary_factor", 1))
    theta = float(params.get("rope_theta", 10000.0))
    j = np.arange(0, r, 2, dtype=np.float64)
    inv = theta ** (-j / r)
    scale = 1.0
    if params.get("rope_type", "default") == "yarn":
        factor = float(params["factor"])
        original = float(params["original_max_position_embeddings"])

        def dim(rotations):
            return r * np.log(original / (2 * np.pi * rotations)) \
                / (2 * np.log(theta))
        low = max(np.floor(dim(float(params.get("beta_fast", 32)))), 0)
        high = min(np.ceil(dim(float(params.get("beta_slow", 1)))), r - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0, 1)
        inv = ramp * inv / factor + (1 - ramp) * inv
        scale = params.get("attention_factor")
        if scale is None:
            scale = 0.1 * np.log(factor) + 1.0
    angles = np.outer(np.arange(positions, dtype=np.float64), inv)
    return np.cos(angles) * scale, np.sin(angles) * scale


def _rope(x, cos, sin):
    """x: [S, H, d] at positions 0..S-1; rotate-half over the leading
    ``2 * cos.shape[-1]`` dims."""
    r = 2 * cos.shape[-1]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., r:]], -1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _project(x, norm, w_q, w_k, w_v, w_g, cos, sin, *, eps):
    """(q [S, H, d] and k [S, H_kv, d] rotated, v [S, H_kv, d], the gate
    [S, H, 1] or 1.0) of ``RMSNorm(x)``."""
    h = _rms_norm(x, norm, eps)
    q = _rope(jnp.einsum("sd,dhk->shk", h, w_q.astype(F32)), cos, sin)
    k = _rope(jnp.einsum("sd,dhk->shk", h, w_k.astype(F32)), cos, sin)
    v = jnp.einsum("sd,dhk->shk", h, w_v.astype(F32))
    # a layer without a gate matrix has no gate
    gate = 1.0 if w_g is None else \
        jax.nn.sigmoid(h @ w_g.astype(F32))[..., None]
    return q, k, v, gate


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_group(q, k, v, *, window):
    """Causal softmax attention of the query heads that read one KV head.
    q: [S, rep, d]; k, v: [S, d]; ``window`` None for a full layer.
    ``Q_BLOCK`` queries are scored at a time."""
    s = q.shape[0]
    scale = q.shape[-1] ** -0.5
    kpos = jnp.arange(s)[None, :]
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        qpos = start + jnp.arange(qb.shape[0])[:, None]
        keep = kpos <= qpos
        if window is not None:
            keep = keep & (kpos > qpos - window)
        scores = jnp.einsum("qrk,sk->rqs", qb, k) * scale
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        outs.append(jnp.einsum("rqs,sv->qrv", probs, v))
    return jnp.concatenate(outs, axis=0)


@jax.jit
def _project_out(x, attn, gate, w_o):
    return x + jnp.einsum("shv,hvd->sd", attn * gate, w_o.astype(F32))


def attention_block(x, norm, w_q, w_k, w_v, w_g, w_o, cos, sin, *, window,
                    eps):
    """x + Attention(RMSNorm(x)). x: [S, D]; w_q: [D, H, d]; w_k, w_v:
    [D, H_kv, d]; w_g: [D, H] or None; w_o: [H, d, D]; ``window`` None for a
    full layer. Query head i reads KV head i // (H / H_kv): one KV head's
    group of query heads is attended at a time, so that a long sequence's
    scores stay small beside the program."""
    q, k, v, gate = _project(x, norm, w_q, w_k, w_v, w_g, cos, sin, eps=eps)
    s, kv_heads, d = k.shape
    q = q.reshape(s, kv_heads, -1, d)
    attn = jnp.concatenate(
        [_attend_group(q[:, g], k[:, g], v[:, g], window=window)
         for g in range(kv_heads)], axis=1)                  # [S, H, d]
    return _project_out(x, attn, gate, w_o)


@functools.partial(jax.jit, static_argnames=("eps",))
def mlp_input(x, norm, *, eps):
    return _rms_norm(x, norm, eps)


@jax.jit
def _gated_mlp_rows(h, w_gate, w_up, w_down):
    g = h @ w_gate.astype(F32)
    u = h @ w_up.astype(F32)
    return (jax.nn.silu(g) * u) @ w_down.astype(F32)


def gated_mlp(h, w_gate, w_up, w_down):
    """``W_down(silu(W_gate h) * W_up h)``, ``TOKEN_BLOCK`` rows at a time."""
    return jnp.concatenate(
        [_gated_mlp_rows(h[t:t + TOKEN_BLOCK], w_gate, w_up, w_down)
         for t in range(0, h.shape[0], TOKEN_BLOCK)], axis=0)


@jax.jit
def router_probs(h, router):
    return jax.nn.softmax(h @ router.astype(F32), axis=-1)


def routing_weights(probs, *, top_k: int, scaling: float):
    """[S, E] combine weights from softmax ``probs`` [S, E]: the ``top_k``
    largest chosen (ties to the lower id), renormalised to sum to 1, times
    ``scaling``; 0 elsewhere."""
    s = probs.shape[0]
    idx = jnp.argsort(-probs, axis=-1, stable=True)[:, :top_k]
    top = jnp.take_along_axis(probs, idx, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[jnp.arange(s)[:, None], idx].set(
        top * scaling)


@jax.jit
def _expert_block(h, combine, gate, up, down):
    """sum_e combine[:, e] * E_e(h) over one block of stacked experts."""
    g = jnp.einsum("sd,edf->esf", h, gate.astype(F32))
    u = jnp.einsum("sd,edf->esf", h, up.astype(F32))
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, down.astype(F32))
    return jnp.einsum("se,esd->sd", combine, out)


def expert_ffn(h, layer: dict, hf: dict):
    """The held experts' part of the routed sum plus the shared expert (where
    the layer has one): the module docstring's held share."""
    combine = routing_weights(
        router_probs(h, layer["router"]),
        top_k=int(hf["num_experts_per_tok"]),
        scaling=float(hf.get("moe_routed_scaling_factor", 1.0)))
    ex = layer["experts"]
    first = int(hf.get("first_expert_held", 0))
    held = ex["gate"].shape[0]
    combine = combine[:, first:first + held]
    parts = []
    for t in range(0, h.shape[0], TOKEN_BLOCK):
        rows = slice(t, t + TOKEN_BLOCK)
        y = jnp.zeros_like(h[rows])
        for e in range(0, held, EXPERT_BLOCK):
            block = slice(e, e + EXPERT_BLOCK)
            y = y + _expert_block(h[rows], combine[rows, block],
                                  ex["gate"][block], ex["up"][block],
                                  ex["down"][block])
        parts.append(y)
    y = jnp.concatenate(parts, axis=0)
    if "shared" in layer:
        sh = layer["shared"]
        y = y + gated_mlp(h, sh["gate"], sh["up"], sh["down"])
    return y


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, norm, head, *, eps):
    """[rows, V]: the head cast to float32 ``VOCAB_BLOCK`` columns at a time
    (100,352 x 3,072 whole would be 1.2 GB beside the program)."""
    h = _rms_norm(x, norm, eps)
    return jnp.concatenate(
        [h @ head[:, v:v + VOCAB_BLOCK].astype(F32)
         for v in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)


def hidden_states(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, D] float32 states after the last layer, before the final norm."""
    eps = float(hf.get("rms_norm_eps", 1e-6))
    tokens = jnp.asarray(tokens, jnp.int32)
    tables = {kind: tuple(jnp.asarray(t, F32) for t in rope_table(
        params, int(hf["head_dim"]), tokens.shape[0]))
        for kind, params in hf["rope_parameters"].items()}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for kind, layer in zip(hf["layer_types"], weights["layers"]):
            cos, sin = tables[kind]
            x = attention_block(
                x, layer["attn_norm"], layer["q"], layer["k"], layer["v"],
                layer.get("g"), layer["o"], cos, sin,
                window=int(hf["sliding_window"]) if kind == SLIDING else None,
                eps=eps)
            h = mlp_input(x, layer["mlp_norm"], eps=eps)
            if "router" in layer:
                x = x + expert_ffn(h, layer, hf)
            else:
                x = x + gated_mlp(h, layer["gate"], layer["up"], layer["down"])
    return x


class Logits:
    """The [S, V] float32 logits of one sequence, a row built when it is
    asked for."""

    def __init__(self, states, norm, head, eps):
        self.states, self.norm, self.head, self.eps = states, norm, head, eps
        self.shape = (states.shape[0], head.shape[-1])

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(self.states[rows], self.norm, self.head,
                               eps=self.eps)

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self[:], dtype)


def logits(weights: dict, hf: dict, tokens) -> Logits:
    """[S, V] float32 logits of one sequence: the full forward pass."""
    return Logits(hidden_states(weights, hf, tokens), weights["final_norm"],
                  weights["head"], float(hf.get("rms_norm_eps", 1e-6)))
