"""Plain reference for ``model_type: xing4_0`` (Xing4.0-29B-A4B): float32
``jax.numpy`` at ``"highest"`` matmul precision, no kernel, no cache, no
batching, and no fold: every key and value is up-projected per head from the
compressed row over the whole sequence, every expert of a block is computed
for every token and then picked, and the hyper-connections are the equations
below as they stand. It sees weights only through ``families/xing4_0.py``
``reference_weights`` and sizes only through the published ``config.json``
keys. One layer's attention, and a block of ``EXPERT_BLOCK`` experts, is cast
to float32 at a time, and queries and tokens go through in blocks, so that
it fits beside the program.

**The residual path** (manifold-constrained hyper-connections, mHC,
arXiv:2512.24880, over hyper-connections, arXiv:2409.19606). Per token the
state is ``X`` in R^{n x C}, ``n = hc_mult``; ``X_0`` is the embedding row
copied ``n`` times. Every layer has two sublayers ``F`` (attention; then the
dense MLP in the first ``first_k_dense_replace`` layers, the experts after),
each with its own pre-norm inside ``F`` and its own ``phi`` [n C, n^2 + 2 n],
``b`` [n^2 + 2 n] and scalars ``alpha`` (pre, post, res):

    r         = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)
    [p; q; R] = r phi
    H_pre     = sigmoid(alpha_pre p + b_pre)
    H_post    = 2 sigmoid(alpha_post q + b_post)
    M         = exp(clip(alpha_res R + b_res, mhc_h_res_clamp_min, _max))
    hc_sinkhorn_iters times: M <- M / (rowsum(M) + hc_eps);
                             M <- M / (colsum(M) + hc_eps)
    u         = sum_j H_pre[j] X_j ;  y = F(u)
    X'_i      = sum_j M[i, j] X_j + H_post[i] y

After the last layer the streams are summed, then the final RMSNorm and the
untied head.

**Attention** (DeepSeek-V3's latent attention, arXiv:2412.19437 section
2.1.1), with ``h = RMSNorm(u)``, H heads, d_n = ``qk_nope_head_dim``, d_r =
``qk_rope_head_dim``, d_v = ``v_head_dim``: ``cq = RMSNorm(W_dq h)``;
``[q_nope_i ; q_rope_i] = W_uq_i cq``; ``[ckv ; kr] = W_dkv h``, ``ckv =
RMSNorm(ckv)``; ``q_rope_i`` and the one ``kr`` all heads share are rotated
over the pairs ``(2j, 2j+1)``; ``[k_nope_i ; v_i] = W_ukv_i ckv``; ``s_i(t, j)
= scale (q_nope_i(t) . k_nope_i(j) + q_rope_i(t) . kr(j))``, causal softmax,
``y = W_o [o_1 .. o_H]``. **YaRN** (arXiv:2309.00071, ``rope_scaling``), in
DeepSeek-V3's convention: pair ``j``'s frequency ``theta^(-2j / d_r)`` is
divided by ``factor`` where its wavelength fits the original context fewer
than ``beta_slow`` times, kept where it fits more than ``beta_fast`` times,
and blended linearly over the pairs between; cos and sin are multiplied by
``m(mscale) / m(mscale_all_dim)`` and ``scale = (d_n + d_r)^-0.5
m(mscale_all_dim)^2``, ``m(a) = 0.1 a ln(factor) + 1``.

**Experts**: ``s = sigmoid(W_g h)``; the ``num_experts_per_tok`` experts with
the largest ``s + b`` (``e_score_correction_bias``; ties to the lower id);
weights ``routed_scaling_factor * s_e / sum_chosen s_e``; ``y = sum_chosen
w_e E_e(h) + E_shared(h)``, each ``E`` a gated MLP.

Departures from the published model, each the configuration file's
``assumed``: the multi-token-prediction module adds nothing to the model's
logits and is left out; the row normalisation comes first and ``hc_eps`` is
added to each sum; the streams are summed at the end; the norm over the
streams has no learned scale; the pairs ``(2j, 2j+1)`` rotate.

``logits`` returns its rows unbuilt (``Logits``): the harness slices the
generated positions off a sequence, and a row is 0.5 MB in float32.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256         # queries scored at a time: [heads, 256, S] floats
EXPERT_BLOCK = 8      # experts cast to float32 at a time
TOKEN_BLOCK = 1024    # tokens through a block of experts at a time
VOCAB_BLOCK = 32768   # columns of the head cast to float32 at a time


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


# --- the rotary part ---------------------------------------------------------

def _mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_tables(hf: dict, positions: int):
    """(cos, sin) [positions, d_r / 2] float32 and the softmax scale."""
    d_r = int(hf["qk_rope_head_dim"])
    theta = float(hf.get("rope_theta", 10000.0))
    pairs = np.arange(0, d_r, 2, dtype=np.float64)
    freq = theta ** (-pairs / d_r)
    scale = (int(hf["qk_nope_head_dim"]) + d_r) ** -0.5
    table_scale = 1.0
    yarn = hf.get("rope_scaling")
    if yarn is not None:
        factor = float(yarn["factor"])
        original = float(yarn["original_max_position_embeddings"])

        def pair_with_rotations(rotations):
            # the pair whose wavelength fits ``original`` ``rotations`` times
            return d_r * math.log(original / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))
        low = max(math.floor(pair_with_rotations(
            float(yarn.get("beta_fast", 32)))), 0)
        high = min(math.ceil(pair_with_rotations(
            float(yarn.get("beta_slow", 1)))), d_r - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(d_r // 2, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        freq = freq / factor * ramp + freq * (1.0 - ramp)
        all_dim = float(yarn.get("mscale_all_dim", 0.0))
        table_scale = _mscale(factor, float(yarn.get("mscale", 1.0))) \
            / _mscale(factor, all_dim)
        scale *= _mscale(factor, all_dim) ** 2
    angles = np.outer(np.arange(positions, dtype=np.float64), freq)
    return (jnp.asarray(np.cos(angles) * table_scale, F32),
            jnp.asarray(np.sin(angles) * table_scale, F32), scale)


def _rope(x, cos, sin):
    """x: [S, ..., d_r] at positions 0..S-1, pairs (2j, 2j+1); cos, sin:
    [S, d_r / 2], broadcast over the axes between."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


# --- the hyper-connections -----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("iters", "eps", "lo", "hi",
                                             "norm_eps"))
def mhc_coefficients(x, phi, bias, alpha, *, iters, eps, lo, hi, norm_eps):
    """(H_pre [S, n], H_post [S, n], H_res [S, n, n]) of the streams ``x``
    [S, n, C]."""
    n = x.shape[1]
    flat = x.reshape(x.shape[0], -1)
    r = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + norm_eps)
    proj = r @ phi.astype(F32)
    alpha, bias = alpha.astype(F32), bias.astype(F32)
    h_pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[:, n:2 * n]
                                  + bias[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * proj[:, 2 * n:] + bias[2 * n:], lo, hi))
    m = m.reshape(-1, n, n)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)     # rows
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)     # columns
    return h_pre, h_post, m


def hyper_connected(x, mixing: dict, hf: dict, sublayer):
    """The streams ``x`` [S, n, C] after ``sublayer`` ([S, C] -> [S, C]
    float32) under ``mixing`` (``phi``, ``bias``, ``alpha``)."""
    h_pre, h_post, h_res = mhc_coefficients(
        x, mixing["phi"], mixing["bias"], mixing["alpha"],
        iters=int(hf["hc_sinkhorn_iters"]), eps=float(hf["hc_eps"]),
        lo=float(hf["mhc_h_res_clamp_min"]),
        hi=float(hf["mhc_h_res_clamp_max"]),
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)))
    y = sublayer(jnp.einsum("sj,sjc->sc", h_pre, x))
    return jnp.einsum("sij,sjc->sic", h_res, x) \
        + h_post[:, :, None] * y[:, None, :]


# --- the sublayers -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d_n", "rank", "eps", "scale"))
def attention(u, norm, q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b, o, cos, sin,
              *, d_n, rank, eps, scale):
    """Attention(RMSNorm(u)). u: [S, D]; q_a: [D, r_q]; q_b: [r_q, H, d_n +
    d_r]; kv_a: [D, rank + d_r]; kv_b: [rank, H, d_n + d_v]; o: [H, d_v, D]."""
    s = u.shape[0]
    h = _rms_norm(u, norm, eps)
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
    kpos = jnp.arange(s)[None, :]
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        keep = kpos <= start + jnp.arange(qb.shape[0])[:, None]
        scores = jnp.einsum("qhk,shk->hqs", qb, k) * scale
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqs,shv->qhv", probs, v))
    return jnp.einsum("shv,hvd->sd", jnp.concatenate(outs, axis=0),
                      o.astype(F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def mlp_input(u, norm, *, eps):
    return _rms_norm(u, norm, eps)


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


def expert_ffn(h, layer: dict, hf: dict):
    """The routed experts' weighted sum plus the shared expert."""
    combine = routing_weights(
        router_scores(h, layer["router"]), layer["router_bias"],
        top_k=int(hf["num_experts_per_tok"]),
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
    return y


@functools.partial(jax.jit, static_argnames=("eps",))
def final_norm(x, norm, *, eps):
    return _rms_norm(x, norm, eps)


@jax.jit
def _head_block(h, head):
    return h @ head.astype(F32)


def head_logits(x, norm, head, *, eps):
    """RMSNorm then the untied head, ``VOCAB_BLOCK`` columns at a time (the
    whole head in float32 is 1.9 GB beside 12.5 GB of program)."""
    h = final_norm(x, norm, eps=eps)
    return jnp.concatenate(
        [_head_block(h, head[:, v:v + VOCAB_BLOCK])
         for v in range(0, head.shape[-1], VOCAB_BLOCK)], axis=-1)


@jax.jit
def next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


# --- the model -----------------------------------------------------------------

def hidden_states(weights: dict, hf: dict, tokens) -> jax.Array:
    """[S, D] float32: the sum of the streams after the last layer, before
    the final norm."""
    eps = float(hf.get("rms_norm_eps", 1e-6))
    tokens = jnp.asarray(tokens, jnp.int32)
    cos, sin, scale = rope_tables(hf, tokens.shape[0])
    n = int(hf["hc_mult"])

    def attn_of(layer):
        return lambda u: attention(
            u, layer["attn_norm"], layer["q_a"], layer["q_a_norm"],
            layer["q_b"], layer["kv_a"], layer["kv_a_norm"], layer["kv_b"],
            layer["o"], cos, sin, d_n=int(hf["qk_nope_head_dim"]),
            rank=int(hf["kv_lora_rank"]), eps=eps, scale=scale)

    def ffn_of(layer):
        def ffn(u):
            h = mlp_input(u, layer["mlp_norm"], eps=eps)
            if "router" in layer:
                return expert_ffn(h, layer, hf)
            return gated_mlp(h, layer["gate"], layer["up"], layer["down"])
        return ffn

    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens].astype(F32)
        x = jnp.broadcast_to(h[:, None, :], (h.shape[0], n, h.shape[1]))
        for layer in weights["layers"]:
            x = hyper_connected(x, layer["hc_attn"], hf, attn_of(layer))
            x = hyper_connected(x, layer["hc_mlp"], hf, ffn_of(layer))
        return jnp.sum(x, axis=1)


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
