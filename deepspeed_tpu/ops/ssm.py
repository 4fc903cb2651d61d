"""The selective state-space recurrence of Mamba-2 (the SSD form: a scalar
decay a head), in plain ``jax.numpy``, and the causal depthwise convolution
before it (which a gated short-convolution layer runs alone, without the
activation: ``causal_conv``).

A head's state ``S`` is ``[head_dim, d_state]``; a token brings ``x``
``[head_dim]``, a step ``dt > 0``, and ``B``, ``C`` ``[groups, d_state]``:
head ``j`` of ``H`` reads those of group ``j // (H / groups)`` (one group in
Granite 4.0-H, eight in Nemotron-H). With ``a_t = exp(dt_t A)``, ``A =
-exp(a_log) < 0``:

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t          y_t = S_t C_t

``ssm_chunk_scan`` sums the same recurrence in closed form over blocks of
``block`` tokens (``L_t = sum_{s<=t} dt_s A`` inside a block):

    y_t = e^{L_t} (S_0 C_t) + sum_{s<=t} e^{L_t - L_s} dt_s (C_t . B_s) x_s
    S_Q = e^{L_Q} S_0 + sum_s e^{L_Q - L_s} dt_s x_s (x) B_s

so a prefill chunk is matrix products (a block's ``[Q, Q]`` scores on the
MXU) and a short carry over the blocks. A row whose ``dt`` is 0 moves nothing:
``a = 1`` and its term vanishes, which is how bucket padding is masked.
``ssm_token_scan`` is the recurrence itself, a token at a time: the oracle of
the chunked form's tests. The skip term ``D x`` is the caller's.

Everything that carries the state is float32 at the highest matmul
precision (a float32 product on a TPU is otherwise one bfloat16 pass): the
state is a sum over thousands of tokens. The scores inside a block take the
operands' own type.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST


#: what may follow a convolution's taps' sum, by the name a caller gives it
ACTIVATIONS = {"silu": jax.nn.silu, None: lambda v: v}


def causal_conv(x, tail, weight, bias=None, activation="silu"):
    """Depthwise causal convolution of width ``K`` over one sequence's rows,
    then ``activation`` (``"silu"``, a Mamba-2 layer's; None: the sum as it
    is, a gated short convolution's). x: [T, C]; tail: [K - 1, C], the rows before
    them; weight: [C, K] (``weight[:, K - 1]`` meets the row itself); bias:
    [C] or None. Returns ([T, C] float32, the rows with the tail before them
    [T + K - 1, C], which the next call's tail is cut from)."""
    k = weight.shape[-1]
    rows = jnp.concatenate([tail.astype(x.dtype), x], axis=0)
    w = weight.astype(F32)
    t = x.shape[0]
    out = sum(rows[j:j + t].astype(F32) * w[:, j] for j in range(k))
    if bias is not None:
        out = bias.astype(F32) + out
    return ACTIVATIONS[activation](out), rows


def split_conv(rows, heads: int, head_dim: int, d_state: int,
               groups: int = 1):
    """(x [T, H, P], B [T, G, N], C [T, G, N]) of the convolved rows ``[x ;
    B ; C]`` [T, H * P + 2 * G * N]."""
    inner, bc = heads * head_dim, groups * d_state
    return (rows[:, :inner].reshape(-1, heads, head_dim),
            rows[:, inner:inner + bc].reshape(-1, groups, d_state),
            rows[:, inner + bc:].reshape(-1, groups, d_state))


def ssm_token_scan(x, dt, a_log, bm, cm, s0):
    """x: [T, H, P]; dt: [T, H]; a_log: [H]; bm, cm: [T, G, N]; s0: [H, P,
    N]. Returns (y [T, H, P], S_T), float32."""
    a_neg = -jnp.exp(a_log.astype(F32))
    rep = x.shape[1] // bm.shape[1]

    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        b_t, c_t = (jnp.repeat(v, rep, axis=0)[:, None, :]
                    for v in (b_t, c_t))                      # [H, 1, N]
        s = jnp.exp(dt_t * a_neg)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t
        return s, jnp.sum(s * c_t, axis=-1)
    s, y = jax.lax.scan(step, s0.astype(F32),
                        (x.astype(F32), dt.astype(F32), bm.astype(F32),
                         cm.astype(F32)))
    return y, s


def ssm_chunk_scan(x, dt, a_log, bm, cm, s0, block: int):
    """``ssm_token_scan`` in closed form over blocks of ``block`` tokens
    (the rows padded to whole blocks with ``dt`` 0). ``x``, ``bm`` and ``cm``
    in the compute type; returns (y [T, H, P] float32, S_T float32)."""
    t, h, p = x.shape
    g, n = bm.shape[1:]
    r = h // g                                   # heads a group of B and C
    q = min(block, t)
    pad = -t % q
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                         for v in (x, dt, bm, cm))
    nc = (t + pad) // q
    dt = dt.astype(F32).reshape(nc, q, h)
    x = x.reshape(nc, q, g, r, p)
    bm, cm = bm.reshape(nc, q, g, n), cm.reshape(nc, q, g, n)
    # L: the block's running log-decay, [nc, Q, H]
    run = jnp.cumsum(dt * -jnp.exp(a_log.astype(F32)), axis=1)
    last = run[:, -1]                                         # [nc, H]

    # inside a block: a group's scores C_t . B_s, and for each of its heads
    # decayed and stepped, times x_s
    scores = jnp.einsum("cqgn,csgn->cgqs", cm, bm, preferred_element_type=F32)
    seen = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    span = run.transpose(0, 2, 1)                             # [nc, H, Q]
    decay = jnp.exp(jnp.where(seen, span[..., :, None] - span[..., None, :],
                              -jnp.inf))                      # [nc, H, Q, S]
    weights = decay * dt.transpose(0, 2, 1)[:, :, None, :]
    weights = scores[:, :, None] * weights.reshape(nc, g, r, q, q)
    y = jnp.einsum("cgrqs,csgrp->cqgrp", weights.astype(x.dtype), x,
                   preferred_element_type=F32)

    # what each block adds to the state, and the carry over the blocks
    toward_end = (jnp.exp(last[:, None] - run) * dt).reshape(nc, q, g, r)
    added = jnp.einsum("cqgrp,cqgn->cgrpn", toward_end[..., None]
                       * x.astype(F32), bm.astype(F32), precision=_EXACT)
    carried = []
    s = s0.astype(F32).reshape(g, r, p, n)
    for c in range(nc):
        carried.append(s)
        s = jnp.exp(last[c]).reshape(g, r, 1, 1) * s + added[c]
    y = y + jnp.exp(run).reshape(nc, q, g, r, 1) * jnp.einsum(
        "cqgn,cgrpn->cqgrp", cm.astype(F32), jnp.stack(carried),
        precision=_EXACT)
    return y.reshape(nc * q, h, p)[:t], s.reshape(h, p, n)
