"""Plain reference for ``model_type: mimo_v2`` (MiMo-V2.5's language model):
float32 ``jax.numpy`` at ``"highest"`` matmul precision, no kernel, no cache,
no batching. It sees weights only through ``families/mimo_v2.py``
``reference_weights`` and sizes only through the published ``config.json``
keys (and the configuration file's ``first_expert_held``). ``Q_BLOCK``
queries of one KV head's group of query heads are scored at a time, and
``EXPERT_BLOCK`` experts, ``WIDTH_BLOCK`` columns of a dense MLP and
``VOCAB_BLOCK`` of the head cast to float32 at a time, each block a program
of its own called from the host, so that it fits in the 1.5 GB the program
leaves it.

Layer ``l`` is full where ``hybrid_layer_pattern[l] == 0`` and windowed where
1: ``H`` query heads over ``H_kv`` KV heads, keys ``d_k`` and values ``d_v``
wide (``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``v_head_dim``; a windowed layer's under the same names with ``swa_``
before them). ``h = RMSNorm(x)`` (eps ``layernorm_epsilon``), no bias:

- ``q = h W_q`` (``H`` x ``d_k``), ``k = h W_k`` (``H_kv`` x ``d_k``),
  ``v = attention_value_scale * (h W_v)`` (``H_kv`` x ``d_v``); query head
  ``i`` reads KV head ``i // (H / H_kv)``.
- rope, rotate-half form, type ``default``: with ``r = int(
  partial_rotary_factor * d_k)`` rounded down to whole pairs (64 of 192), dim
  ``j < r / 2`` pairs with ``j + r / 2`` at ``inv_freq_j = theta ** (-2j /
  r)``, ``theta = rope_theta`` in a full layer and ``swa_rope_theta`` in a
  windowed one; the dims from ``r`` on pass through.
- scores ``s_tj = q_t . k_j / sqrt(d_k)`` over ``j <= t``, in a windowed
  layer ``j > t - sliding_window`` too. Without sinks ``p = softmax_j(s)``.
  With (``add_swa_attention_sink_bias`` the windowed layers,
  ``add_full_attention_sink_bias`` the full ones), head ``n`` with ``b_n``:
  ``p_tj = exp(s_tj) / (exp(b_n) + sum_j' exp(s_tj'))``: the sink joins the
  denominator and has no value. ``o_t = sum_j p_tj v_j``; ``x <- x +
  concat_heads(o) W_o``.
- ``h2 = RMSNorm(x)``. Where ``moe_layer_freq[l] == 0``: ``x <- x +
  W_down(silu(W_gate h2) * W_up h2)``. Else ``c = sigmoid(h2 W_r)`` over the
  router's whole width; the ``num_experts_per_tok`` largest of ``c +
  e_score_correction_bias``, ties to the lower id; ``w = c_sel / sum(c_sel)``
  (``norm_topk_prob``) times ``routed_scaling_factor`` (null: 1); ``x <- x +
  sum_k w_k E_k(h2)``, ``E`` gated-silu MLPs; no shared expert.
- head: RMSNorm, the untied ``lm_head``.

**The held share.** The weights' stack of experts may be a share of the
router's: experts ``first_expert_held .. first_expert_held + E - 1`` of the
``W_r.shape[1]`` the router scores. The sum then runs over the chosen experts
that are held (the absent chips add the rest): what guide section 4 asks the
reference of a share cut to compute. With every expert held it is the model.

Departures and readings, each under ``assumed`` in the configuration file:
the sink's form and that it is one scalar a query head; the value scale on V
in both kinds of layer; ``attention_chunk_size`` unread (a windowed layer is
a sliding window); no q/k norm; the multi-token-prediction layers and the
encoders unbuilt.

``logits`` returns its rows unbuilt (``Logits``): the harness slices the
generated positions off a sequence, and a row of 152,576 logits is 0.6 MB.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 128         # queries scored at a time: [group, 128, S] floats
EXPERT_BLOCK = 4      # experts cast to float32 at a time
TOKEN_BLOCK = 1024    # tokens through a block of experts or columns at a time
WIDTH_BLOCK = 4096    # columns of a dense MLP cast to float32 at a time
VOCAB_BLOCK = 16384   # columns of the head cast to float32 at a time


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def layer_shape(hf: dict, windowed: bool) -> dict:
    """{heads, kv_heads, d_k, d_v} of a layer kind."""
    pre = "swa_" if windowed else ""
    return {"heads": int(hf[pre + "num_attention_heads"]),
            "kv_heads": int(hf[pre + "num_key_value_heads"]),
            "d_k": int(hf[pre + "head_dim"]),
            "d_v": int(hf[pre + "v_head_dim"])}


def rotary_dim(hf: dict, d_k: int) -> int:
    return int(float(hf.get("partial_rotary_factor", 1.0)) * d_k) // 2 * 2


def rope_table(theta: float, r: int, positions: int):
    """(cos, sin) float64 [positions, r / 2]: the module docstring's
    ``default`` formula."""
    inv = float(theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angles = np.outer(np.arange(positions, dtype=np.float64), inv)
    return np.cos(angles), np.sin(angles)


def _rope(x, cos, sin):
    """x: [S, H, d] at positions 0..S-1; rotate-half over the leading
    ``2 * cos.shape[-1]`` dims."""
    r = 2 * cos.shape[-1]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., r:]], -1)


@functools.partial(jax.jit, static_argnames=("eps", "value_scale"))
def _project(x, norm, w_q, w_k, w_v, cos, sin, *, eps, value_scale):
    """(q [S, H, d_k] and k [S, H_kv, d_k] rotated, v [S, H_kv, d_v] scaled)
    of ``RMSNorm(x)``."""
    h = _rms_norm(x, norm, eps)
    q = _rope(jnp.einsum("sd,dhk->shk", h, w_q.astype(F32)), cos, sin)
    k = _rope(jnp.einsum("sd,dhk->shk", h, w_k.astype(F32)), cos, sin)
    v = jnp.einsum("sd,dhk->shk", h, w_v.astype(F32)) * value_scale
    return q, k, v


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_rows(qb, k, v, sink, start, *, window):
    """Causal attention of ``Q_BLOCK`` queries at positions ``start ..`` of
    the query heads that read one KV head. qb: [Q_BLOCK, rep, d_k]; k:
    [S, d_k]; v: [S, d_v]; ``sink`` [rep] or None; ``window`` None for a full
    layer. One compiled program a sequence length: ``start`` is a value."""
    scale = qb.shape[-1] ** -0.5
    kpos = jnp.arange(k.shape[0])[None, :]
    qpos = start + jnp.arange(qb.shape[0])[:, None]
    keep = kpos <= qpos
    if window is not None:
        keep = keep & (kpos > qpos - window)
    scores = jnp.einsum("qrk,sk->rqs", qb, k) * scale
    scores = jnp.where(keep, scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    if sink is not None:
        top = jnp.maximum(top, sink[:, None, None])
    e = jnp.exp(scores - top)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(sink[:, None, None] - top)
    return jnp.einsum("rqs,sv->qrv", e / denom, v)


def _attend_group(q, k, v, sink, *, window):
    """``_attend_rows`` over a sequence's queries, ``Q_BLOCK`` at a time from
    the host (a block's scores are [rep, Q_BLOCK, S] floats, and no two
    blocks' are alive at once). q: [S, rep, d_k]."""
    s = q.shape[0]
    q = jnp.pad(q, ((0, -s % Q_BLOCK), (0, 0), (0, 0)))
    return jnp.concatenate(
        [_attend_rows(q[start:start + Q_BLOCK], k, v, sink, start,
                      window=window)
         for start in range(0, s, Q_BLOCK)], axis=0)[:s]


@jax.jit
def _project_out(x, attn, w_o):
    return x + jnp.einsum("shv,hvd->sd", attn, w_o.astype(F32))


def attention_block(x, layer: dict, cos, sin, *, window, eps, value_scale):
    """x + Attention(RMSNorm(x)). x: [S, D]; ``layer``: q [D, H, d_k], k
    [D, H_kv, d_k], v [D, H_kv, d_v], o [H, d_v, D], and ``sink`` [H] where
    the layer has sinks; ``window`` None for a full layer. Query head i reads
    KV head i // (H / H_kv): one KV head's group is attended at a time."""
    q, k, v = _project(x, layer["attn_norm"], layer["q"], layer["k"],
                       layer["v"], cos, sin, eps=eps,
                       value_scale=value_scale)
    s, kv_heads, d_k = k.shape
    q = q.reshape(s, kv_heads, -1, d_k)
    sink = layer.get("sink")
    if sink is not None:
        sink = sink.astype(F32).reshape(kv_heads, -1)
    attn = jnp.concatenate(
        [_attend_group(q[:, g], k[:, g], v[:, g],
                       None if sink is None else sink[g], window=window)
         for g in range(kv_heads)], axis=1)                  # [S, H, d_v]
    return _project_out(x, attn, layer["o"])


@functools.partial(jax.jit, static_argnames=("eps",))
def mlp_input(x, norm, *, eps):
    return _rms_norm(x, norm, eps)


@jax.jit
def _gated_mlp_rows(h, w_gate, w_up, w_down):
    g = h @ w_gate.astype(F32)
    u = h @ w_up.astype(F32)
    return (jax.nn.silu(g) * u) @ w_down.astype(F32)


def gated_mlp(h, w_gate, w_up, w_down):
    """``W_down(silu(W_gate h) * W_up h)``, ``TOKEN_BLOCK`` rows and
    ``WIDTH_BLOCK`` of the hidden columns at a time (the gated product is
    elementwise in them, and ``W_down`` sums over them)."""
    rows = []
    for t in range(0, h.shape[0], TOKEN_BLOCK):
        ht = h[t:t + TOKEN_BLOCK]
        rows.append(sum(
            _gated_mlp_rows(ht, w_gate[:, c:c + WIDTH_BLOCK],
                            w_up[:, c:c + WIDTH_BLOCK],
                            w_down[c:c + WIDTH_BLOCK])
            for c in range(0, w_gate.shape[1], WIDTH_BLOCK)))
    return jnp.concatenate(rows, axis=0)


@jax.jit
def router_scores(h, router):
    return jax.nn.sigmoid(h @ router.astype(F32))


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
def _expert_block(h, combine, gate, up, down):
    """sum_e combine[:, e] * E_e(h) over one block of stacked experts."""
    g = jnp.einsum("sd,edf->esf", h, gate.astype(F32))
    u = jnp.einsum("sd,edf->esf", h, up.astype(F32))
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, down.astype(F32))
    return jnp.einsum("se,esd->sd", combine, out)


def expert_ffn(h, layer: dict, hf: dict):
    """The held experts' part of the routed sum: the module docstring's held
    share."""
    combine = routing_weights(
        router_scores(h, layer["router"]), layer["router_bias"],
        top_k=int(hf["num_experts_per_tok"]),
        scaling=float(hf.get("routed_scaling_factor") or 1.0))
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
    return jnp.concatenate(parts, axis=0)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_input(x, norm, *, eps):
    return _rms_norm(x, norm, eps)


@jax.jit
def _head_block(h, head):
    return h @ head.astype(F32)


def head_logits(x, norm, head, *, eps):
    """[rows, V]: the head cast to float32 ``VOCAB_BLOCK`` columns at a time,
    from the host (152,576 x 4,096 whole would be 2.5 GB beside the
    program)."""
    h = _head_input(x, norm, eps=eps)
    return jnp.concatenate(
        [_head_block(h, head[:, v:v + VOCAB_BLOCK])
         for v in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)


def _eps(hf: dict) -> float:
    return float(hf.get("layernorm_epsilon", hf.get("rms_norm_eps", 1e-5)))


def hidden_states(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, D] float32 states after the last layer, before the final norm."""
    eps = _eps(hf)
    tokens = jnp.asarray(tokens, jnp.int32)
    tables = {}
    for windowed in (False, True):
        d_k = layer_shape(hf, windowed)["d_k"]
        theta = hf["swa_rope_theta"] if windowed else hf["rope_theta"]
        tables[windowed] = tuple(jnp.asarray(t, F32) for t in rope_table(
            theta, rotary_dim(hf, d_k), tokens.shape[0]))
    value_scale = float(hf.get("attention_value_scale") or 1.0)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for kind, dense, layer in zip(hf["hybrid_layer_pattern"],
                                      hf["moe_layer_freq"],
                                      weights["layers"]):
            cos, sin = tables[kind == 1]
            x = attention_block(
                x, layer, cos, sin,
                window=int(hf["sliding_window"]) if kind == 1 else None,
                eps=eps, value_scale=value_scale)
            h = mlp_input(x, layer["mlp_norm"], eps=eps)
            if dense == 0:
                x = x + gated_mlp(h, layer["gate"], layer["up"],
                                  layer["down"])
            else:
                x = x + expert_ffn(h, layer, hf)
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
                  weights["head"], _eps(hf))
