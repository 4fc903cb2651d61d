"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): ``n`` residual streams round a
sublayer ``F`` in the place of ``x + F(norm(x))``.

Per token, streams ``X`` [n, C] and a sublayer's parameters ``phi``
[n C, n^2 + 2 n], ``bias`` [n^2 + 2 n] and ``alpha`` [3] (pre, post, res):

    r         = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)     over all n C values
    [p; q; R] = r phi                                        n, n, n x n
    H_pre     = sigmoid(alpha_pre p + b_pre)
    H_post    = 2 sigmoid(alpha_post q + b_post)
    M         = exp(clip(alpha_res R + b_res, clamp))
    iters times:  M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps)
    u         = sum_j H_pre[j] X_j        ;   y = F(u)
    X'_i      = sum_j M[i, j] X_j + H_post[i] y

``M`` is doubly stochastic after the Sinkhorn-Knopp iterations, so the sum of
the streams obeys ``sum_i X'_i = sum_i X_i + (sum_i H_post[i]) y`` whatever
the weights. The coefficients are computed in float32 from the streams as
they are kept (the model's dtype); the weighted sums are taken in float32
and rounded once. Rows that are bucket padding go through like any other:
the clamp keeps them finite.

The one definition of the mixing, beside the policy that serves it (the
served scopes are opened under ``inference/v2``): ``models/xing4.py`` (the plain forward)
and ``inference/v2/modules.py`` (``Xing4Policy``) call it, under the scopes
``hc/pre``, ``hc/post`` and ``hc/head`` that are opened here.
"""

import dataclasses

import jax
import jax.numpy as jnp

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class HyperConnection:
    """The constants of the mixing (``hc_mult``, ``hc_sinkhorn_iters``,
    ``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``, and the eps of the norm
    over the streams, ``rms_norm_eps``)."""
    streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp_min: float = -30.0
    clamp_max: float = 30.0
    norm_eps: float = 1e-6

    @property
    def coefficients(self) -> int:
        """Columns of ``phi``: H_pre, H_post and H_res of one token."""
        return self.streams * (self.streams + 2)


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of row then column normalisation of ``m``
    [..., n, n] (positive). Written out: 80 small device operations a
    sublayer that cost a 64-row decode program nothing measurable against
    18 (PERF.md section 6, PR 37), and compile in a third of the time."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def coefficients(x, params, hc: HyperConnection):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) in float32 from the
    streams ``x`` [T, n, C] and a sublayer's ``params`` (``phi``, ``bias``,
    ``alpha``)."""
    n = hc.streams
    flat = x.reshape(x.shape[0], -1).astype(F32)
    r = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + hc.norm_eps)
    proj = jnp.dot(r, params["phi"].astype(F32),
                   precision=jax.lax.Precision.HIGHEST)
    alpha, bias = params["alpha"].astype(F32), params["bias"].astype(F32)
    h_pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[:, n:2 * n] + bias[n:2 * n])
    res = alpha[2] * proj[:, 2 * n:] + bias[2 * n:]
    m = jnp.exp(jnp.clip(res, hc.clamp_min, hc.clamp_max)).reshape(-1, n, n)
    return h_pre, h_post, sinkhorn(m, hc.sinkhorn_iters, hc.eps)


def pre_mix(x, params, hc: HyperConnection):
    """(the sublayer's input ``u`` [T, C] in ``x``'s dtype, (H_post, H_res)
    for ``post_mix``) from the streams ``x`` [T, n, C]."""
    with jax.named_scope("hc/pre"):
        h_pre, h_post, h_res = coefficients(x, params, hc)
        u = sum(h_pre[:, j, None] * x[:, j].astype(F32)
                for j in range(hc.streams))
        return u.astype(x.dtype), (h_post, h_res)


def post_mix(x, y, mix, hc: HyperConnection):
    """The streams after the sublayer: ``H_res X + H_post y`` ([T, n, C], in
    ``x``'s dtype) from the streams before it, its output ``y`` [T, C] and
    ``pre_mix``'s ``mix``."""
    h_post, h_res = mix
    with jax.named_scope("hc/post"):
        y32 = y.astype(F32)
        rows = [sum(h_res[:, i, j, None] * x[:, j].astype(F32)
                    for j in range(hc.streams)) + h_post[:, i, None] * y32
                for i in range(hc.streams)]
        return jnp.stack(rows, axis=1).astype(x.dtype)


def expand(h, hc: HyperConnection):
    """The first layer's streams: ``h`` [..., C] copied ``n`` times,
    [..., n, C]."""
    return jnp.broadcast_to(h[..., None, :],
                            h.shape[:-1] + (hc.streams, h.shape[-1]))


def collapse(x):
    """What the head reads: the sum of the streams ``x`` [..., n, C], taken
    in float32."""
    with jax.named_scope("hc/head"):
        return jnp.sum(x.astype(F32), axis=-2).astype(x.dtype)


def init_params(key, hc: HyperConnection, width: int):
    """A sublayer's parameters as the papers start them: ``alpha`` 0.01,
    ``phi`` a unit-variance projection of the normed streams, and biases that
    make H_pre ``1 / n``, H_post 1 and H_res the identity to within e^-8, so
    that a fresh model is a plain residual over the mean of its streams."""
    n = hc.streams
    phi = jax.random.normal(key, (n * width, hc.coefficients), F32) \
        * (n * width) ** -0.5
    b_pre = jnp.full((n,), -jnp.log(n - 1.0) if n > 1 else 30.0, F32)
    b_res = (jnp.eye(n, dtype=F32) - 1.0) * 8.0
    return {"phi": phi, "alpha": jnp.full((3,), 0.01, F32),
            "bias": jnp.concatenate([b_pre, jnp.zeros((n,), F32),
                                     b_res.reshape(-1)])}
