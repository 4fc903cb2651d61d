"""Plain reference for ``model_type: joyai_llm_flash`` (JoyAI-LLM-Flash,
48B-A2.7B; the layers are DeepSeek-V3's, arXiv:2412.19437 sections 2.1.1 and
2.1.2, at other sizes): float32 ``jax.numpy`` at ``"highest"`` matmul
precision, no kernel, no cache, no batching, and no fold: every key and value
is up-projected per head from the compressed row, so that agreement with the
program (which in decode attends over the compressed rows themselves, with
the up-projections folded into the query and the output) proves the fold. It
sees weights only through ``families/joyai_llm_flash.py``
``reference_weights`` and sizes only through the published ``config.json``
keys. One layer's attention, and a block of ``EXPERT_BLOCK`` experts, is cast
to float32 at a time: a layer's 256 experts whole are 4.8 GB in float32.

With ``h = RMSNorm(x)`` (eps ``rms_norm_eps``), H heads, d_n =
``qk_nope_head_dim``, d_r = ``qk_rope_head_dim``, d_v = ``v_head_dim``:

- attention, every layer, token t, head i: ``cq = RMSNorm(W_dq h)``;
  ``[q_nope_i ; q_rope_i] = W_uq_i cq``; ``[ckv ; kr] = W_dkv h``, ``ckv =
  RMSNorm(ckv)``; ``q_rope_i = RoPE(q_rope_i, t)``, ``kr = RoPE(kr, t)`` over
  the pairs ``(2j, 2j+1)`` with ``rope_theta``, ``kr`` shared by all heads;
  ``[k_nope_i ; v_i] = W_ukv_i ckv``; ``s_i(t, j) = (q_nope_i(t) . k_nope_i(j)
  + q_rope_i(t) . kr(j)) / sqrt(d_n + d_r)``, causal softmax over j <= t;
  ``o_i = sum_j p_i(t, j) v_i(j)``; ``x += W_o [o_1 .. o_H]``.
- the first ``first_k_dense_replace`` layers: ``x += W_down(silu(W_gate h) *
  W_up h)``.
- the others: ``s = sigmoid(W_g h)``; the ``num_experts_per_tok`` experts
  with the largest ``s + b`` (``b`` = ``e_score_correction_bias``; ties to the
  lower id); weights ``w_e = routed_scaling_factor * s_e / sum_chosen s_e``
  (``b`` does not enter them); ``x += sum_chosen w_e E_e(h) + E_shared(h)``,
  each ``E`` a gated MLP.
- head: RMSNorm, the untied ``lm_head``.

Departures from the published model: the multi-token-prediction module
(``num_nextn_predict_layers``) adds nothing to the model's logits and is left
out. Every expert of a block is computed for every token and masked by the
combine weights: plain, not fast.

**Router near-ties.** The choice of experts is a discontinuous function of
the state: where the eighth and the ninth largest ``s + b`` of a token lie
closer than two sound computations of ``s`` differ (bfloat16 states against
float32 ones), the two choose different experts, and that token's logits
then differ by what the two experts differ in. ``router_margins`` gives
every position its smallest such gap over the expert layers, for whoever
wants to tell a near-tie from an error (PERF.md section 6, PR 31, has the
published sizes' readings); ``logits`` are the model's at every position.

``logits`` returns its rows unbuilt (``Logits``): the harness slices the
generated positions off a sequence, and a row is 0.5 MB in float32, so only
the rows asked for are multiplied by the head (an 8k sequence's whole would
be 4.2 GB).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256         # queries scored at a time: [heads, 256, S] floats
EXPERT_BLOCK = 16     # experts cast to float32 at a time
TOKEN_BLOCK = 2048    # tokens through a block of experts at a time


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, cos, sin):
    """x: [S, ..., d_r] at positions 0..S-1, pairs (2j, 2j+1); cos, sin:
    [S, d_r / 2], broadcast over the axes between."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("d_n", "rank", "eps"))
def attention_block(x, norm, q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b, o,
                    cos, sin, *, d_n, rank, eps):
    """x + Attention(RMSNorm(x)). x: [S, D]; q_a: [D, r_q]; q_b: [r_q, H,
    d_n + d_r]; kv_a: [D, rank + d_r]; kv_b: [rank, H, d_n + d_v]; o:
    [H, d_v, D]."""
    s = x.shape[0]
    h = _rms_norm(x, norm, eps)
    cq = _rms_norm(h @ q_a.astype(F32), q_a_norm, eps)
    q = jnp.einsum("sr,rhk->shk", cq, q_b.astype(F32))
    ckv = h @ kv_a.astype(F32)
    kv = jnp.einsum("sr,rhk->shk", _rms_norm(ckv[:, :rank], kv_a_norm, eps),
                    kv_b.astype(F32))
    k_r = _rope(ckv[:, rank:], cos, sin)                       # [S, d_r]
    q = jnp.concatenate([q[..., :d_n], _rope(q[..., d_n:], cos, sin)], -1)
    k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(
        k_r[:, None, :], kv.shape[:2] + k_r.shape[-1:])], -1)
    v = kv[..., d_n:]
    scale = q.shape[-1] ** -0.5
    kpos = jnp.arange(s)[None, :]
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        keep = kpos <= start + jnp.arange(qb.shape[0])[:, None]
        scores = jnp.einsum("qhk,shk->hqs", qb, k) * scale
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqs,shv->qhv", probs, v))
    attn = jnp.concatenate(outs, axis=0)
    return x + jnp.einsum("shv,hvd->sd", attn, o.astype(F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def mlp_input(x, norm, *, eps):
    return _rms_norm(x, norm, eps)


@jax.jit
def gated_mlp(h, w_gate, w_up, w_down):
    g = h @ w_gate.astype(F32)
    u = h @ w_up.astype(F32)
    return (jax.nn.silu(g) * u) @ w_down.astype(F32)


@jax.jit
def router_scores(h, router):
    return jax.nn.sigmoid(h @ router.astype(F32))


def routing_weights(scores, bias, *, top_k: int, scaling: float):
    """[S, E] combine weights from sigmoid ``scores`` [S, E]: the ``top_k``
    largest of ``scores + bias`` chosen (ties to the lower id), each at its
    own score (without the bias), renormalised to sum to 1, times
    ``scaling``; 0 elsewhere."""
    s = scores.shape[0]
    idx = jnp.argsort(-(scores + bias.astype(F32)), axis=-1,
                      stable=True)[:, :top_k]
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], idx].set(
        top * scaling)


@jax.jit
def _expert_block(h, combine, gate, up, down):
    """sum_e combine[:, e] * E_e(h) over one block of stacked experts."""
    g = jnp.einsum("sd,edf->esf", h, gate.astype(F32))
    u = jnp.einsum("sd,edf->esf", h, up.astype(F32))
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, down.astype(F32))
    return jnp.einsum("se,esd->sd", combine, out)


def selection_margin(scores, bias, top_k: int):
    """[S]: the gap between the ``top_k``-th and the next largest of
    ``scores + bias``: how far a token's choice of experts is from another."""
    ranked = jnp.sort(scores + bias.astype(F32), axis=-1)
    return ranked[:, -top_k] - ranked[:, -top_k - 1]


def expert_ffn(h, layer: dict, hf: dict):
    """(the routed experts' weighted sum plus the shared expert, the
    selection margin of every token)."""
    top_k = int(hf["num_experts_per_tok"])
    scores = router_scores(h, layer["router"])
    combine = routing_weights(
        scores, layer["router_bias"], top_k=top_k,
        scaling=float(hf.get("routed_scaling_factor", 1.0)))
    ex = layer["experts"]
    parts = []
    for t in range(0, h.shape[0], TOKEN_BLOCK):
        rows = slice(t, t + TOKEN_BLOCK)
        y = jnp.zeros_like(h[rows])
        for e in range(0, ex["gate"].shape[0], EXPERT_BLOCK):
            block = slice(e, e + EXPERT_BLOCK)
            y = y + _expert_block(h[rows], combine[rows, block],
                                  ex["gate"][block], ex["up"][block],
                                  ex["down"][block])
        parts.append(y)
    y = jnp.concatenate(parts, axis=0)
    if "shared" in layer:
        sh = layer["shared"]
        y = y + gated_mlp(h, sh["gate"], sh["up"], sh["down"])
    return y, selection_margin(scores, layer["router_bias"], top_k)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, norm, head, *, eps):
    return _rms_norm(x, norm, eps) @ head.astype(F32)


@jax.jit
def next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def _forward(weights: dict, hf: dict, tokens):
    """([S, D] float32 states after the last layer, before the final norm;
    [S] the smallest selection margin over the expert layers)."""
    eps = float(hf.get("rms_norm_eps", 1e-6))
    tokens = jnp.asarray(tokens, jnp.int32)
    d_r = int(hf["qk_rope_head_dim"])
    freq = float(hf.get("rope_theta", 10000.0)) ** (
        -np.arange(0, d_r, 2, dtype=np.float64) / d_r)
    angles = np.outer(np.arange(tokens.shape[0], dtype=np.float64), freq)
    cos, sin = jnp.asarray(np.cos(angles), F32), jnp.asarray(np.sin(angles), F32)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for layer in weights["layers"]:
            x = attention_block(
                x, layer["attn_norm"], layer["q_a"], layer["q_a_norm"],
                layer["q_b"], layer["kv_a"], layer["kv_a_norm"],
                layer["kv_b"], layer["o"], cos, sin,
                d_n=int(hf["qk_nope_head_dim"]), rank=int(hf["kv_lora_rank"]),
                eps=eps)
            h = mlp_input(x, layer["mlp_norm"], eps=eps)
            if "router" in layer:
                y, gap = expert_ffn(h, layer, hf)
                x, margin = x + y, jnp.minimum(margin, gap)
            else:
                x = x + gated_mlp(h, layer["gate"], layer["up"], layer["down"])
    return x, margin


def hidden_states(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, D] float32 states after the last layer, before the final norm."""
    return _forward(weights, hf, tokens)[0]


def router_margins(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S]: every position's smallest gap, over the expert layers, between
    the last chosen expert's ``s + b`` and the first left out's."""
    return _forward(weights, hf, tokens)[1]


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


def loss(weights: dict, hf: dict, rows) -> float:
    """Mean next-token cross-entropy over rows of equal length."""
    losses = [float(next_token_loss(logits(weights, hf, row)[:],
                                    jnp.asarray(row, jnp.int32)))
              for row in rows]
    return float(np.mean(losses))
