"""Pallas flash-attention kernels (forward + backward).

Reference analog: the fused attention CUDA kernels
(``csrc/transformer/inference/csrc/softmax.cu``, the training transformer kernel
suite ``csrc/transformer/`` fused fwd+bwd, v2 ``blocked_flash``). TPU design:
canonical sequential-grid flash — grid (batch*heads, q_blocks, k_blocks) with the
k dimension innermost (TPU grids execute sequentially, so VMEM scratch accumulators
carry across k steps): online-softmax max/sum/output accumulators in fp32 scratch,
[block_q, block_k] score panels on the MXU, GQA handled by index-mapping q heads
onto shared KV heads (no KV repeat materialized).

Causal block skipping: score blocks entirely above the diagonal are predicated
out with ``pl.when`` — the MXU work for the ~half of blocks that are fully
masked is skipped (the reference's fused kernels get the same effect from their
triangular launch bounds).

Backward: FlashAttention-2 style two-kernel recompute. The forward additionally
emits the per-row logsumexp; backward precomputes ``delta = rowsum(dO * O)``
with XLA, then
- a dQ kernel over grid (B*H, q_blocks, k_blocks) accumulating
  ``dq += ds @ K`` in fp32 VMEM scratch, and
- a dKV kernel over grid (B*Hkv, k_blocks, q_blocks * group) accumulating
  ``dk += ds^T @ Q`` / ``dv += p^T @ dO`` — the GQA group dimension is folded
  into the innermost grid axis so gradients for KV heads shared by several query
  heads accumulate in-kernel (no rep-times-larger intermediate in HBM).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.flash_attention import flash_attention as blockwise_reference

NEG_INF = -1e30


def _masked_scores(q, k, qi, ki, *, sm_scale, causal, block_q, block_k,
                   seq_len_k, window=None, causal_shift=0,
                   qseg=None, kseg=None):
    """Shared score-panel + mask construction for the forward and both backward
    kernels — keeps their masking numerically locked together. Returns
    (s[bq,bk] fp32 scores, mask[bq,bk] bool: kv-padding AND causal AND
    mistral-style sliding ``window``: token t sees (t-window, t])."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_len_k
    if causal or window is not None:
        # a window implies the causal band (t-window, t] — same contract as
        # attention_reference/_xla_attention. ``causal_shift=1`` is the
        # STRICT band (qpos > kpos): striped ring attention steps where the
        # KV stripe sits one position ahead of the query stripe.
        mask = jnp.logical_and(mask, qpos >= kpos + causal_shift)
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    if qseg is not None:
        # packed sequences: tokens attend within their segment only
        mask = jnp.logical_and(mask, qseg == kseg.reshape(1, -1))
    return s, mask


def _block_live(qi, ki, *, causal, block_q, block_k, window):
    """Whether a [block_q, block_k] panel can contain any unmasked entry —
    the pl.when skip shared by all three kernels: blocks entirely above the
    causal diagonal AND blocks entirely below the sliding window are dead."""
    live = None
    if causal or window is not None:   # window implies the causal band
        live = ki * block_k <= qi * block_q + block_q - 1
    if window is not None:
        w_live = (ki + 1) * block_k - 1 > qi * block_q - window
        live = jnp.logical_and(live, w_live)
    return live


def _flash_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr, *,
                  sm_scale, causal, block_q, block_k, num_k_blocks, seq_len_k,
                  window=None, causal_shift=0, has_seg=False):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0]                       # [block_q, D]
        k = k_ref[0]                       # [block_k, D]
        v = v_ref[0]
        s, mask = _masked_scores(q, k, qi, ki, sm_scale=sm_scale, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 seq_len_k=seq_len_k, window=window,
                                 causal_shift=causal_shift,
                                 qseg=qs_ref[0] if has_seg else None,
                                 kseg=ks_ref[0] if has_seg else None)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]                  # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new
        acc_scr[:] = acc

    live = _block_live(qi, ki, causal=causal, block_q=block_q,
                       block_k=block_k, window=window)
    if live is None:
        _compute()
    else:
        pl.when(live)(_compute)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _fold(x):
    """[B, S, H, D] -> [B*H, S, D]."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _pad_seq(x, block):
    pad = (-x.shape[1]) % block
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else x


def _unfold(x, b, h, s):
    return x.reshape(b, h, x.shape[1], x.shape[2]).transpose(0, 2, 1, 3)[:, :s]


def _seg_operands(segment_ids, sq, sk, block_q, block_k):
    """Padded [B, S, 1] int32 segment arrays (+has_seg). ``segment_ids`` is
    [B, S] shared by q and k, or a ``(q_ids [B, Sq], k_ids [B, Sk])`` pair
    (ring attention: the rotating KV block carries different ids than the
    local queries). Padding uses -1 on the k side so padded keys mismatch
    every real segment (they are also masked by seq_len_k)."""
    if segment_ids is None:
        return (jnp.zeros((1, block_q, 1), jnp.int32),
                jnp.zeros((1, block_k, 1), jnp.int32), False)
    if isinstance(segment_ids, tuple):
        q_ids, k_ids = segment_ids
    else:
        q_ids = k_ids = segment_ids
    qs = jnp.pad(jnp.asarray(q_ids, jnp.int32),
                 ((0, 0), (0, (-sq) % block_q)),
                 constant_values=-1)[..., None]
    ks = jnp.pad(jnp.asarray(k_ids, jnp.int32)[:, :sk],
                 ((0, 0), (0, (-sk) % block_k)),
                 constant_values=-1)[..., None]
    return qs, ks, True


def _seg_specs(has_seg, h_of, block_q, block_k, q_major=True):
    """Block specs for the (q_seg, k_seg) operands: indexed by BATCH
    (grid dim0 // heads). ``q_major``: grid is (g, q_blocks, k_blocks);
    otherwise (g, k_blocks, q_steps) — the dkv layout."""
    if not has_seg:
        z = lambda bh, i, j: (0, 0, 0)
        return [pl.BlockSpec((1, block_q, 1), z),
                pl.BlockSpec((1, block_k, 1), z)]
    if q_major:
        return [pl.BlockSpec((1, block_q, 1),
                             lambda bh, i, j: (h_of(bh), i, 0)),
                pl.BlockSpec((1, block_k, 1),
                             lambda bh, i, j: (h_of(bh), j, 0))]
    return [pl.BlockSpec((1, block_q, 1),
                         lambda bh, i, j: (h_of(bh), j, 0)),
            pl.BlockSpec((1, block_k, 1),
                         lambda bh, i, j: (h_of(bh), i, 0))]


def _pallas_flash_fwd_impl(q, k, v, causal: bool, block_q: int, block_k: int,
                           interpret: bool, window=None, causal_shift=0,
                           segment_ids=None):
    """q: [B, Sq, H, D]; k,v: [B, Sk, Hkv, D] -> (out, lse[B*H, Sq_padded])."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    sm_scale = 1.0 / np.sqrt(d)

    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v, block_k)
    sq_p, sk_p = qp.shape[1], kp.shape[1]
    q2, k2, v2 = _fold(qp), _fold(kp), _fold(vp)
    qs, ks, has_seg = _seg_operands(segment_ids, sq, sk, block_q, block_k)

    nq, nk = sq_p // block_q, sk_p // block_k
    grid = (b * h, nq, nk)

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          seq_len_k=sk, window=window,
                          causal_shift=causal_shift, has_seg=has_seg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j, rep=rep: (bh // rep, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j, rep=rep: (bh // rep, j, 0)),
        ] + _seg_specs(has_seg, lambda bh, h=h: bh // h, block_q, block_k),
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            # rank-3 [B*H, S, 1]: TPU blocks need sublane %8 == 0 and lane
            # equal to the array dim — a rank-2 (1, block_q) block is rejected
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q2, k2, v2, qs, ks)

    return _unfold(out, b, h, sq), lse


def _dq_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, *,
               sm_scale, causal, block_q, block_k, num_k_blocks, seq_len_k,
               window=None, causal_shift=0, has_seg=False):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0]                   # [block_q, 1]
        delta = delta_ref[0]               # [block_q, 1]
        s, mask = _masked_scores(q, k, qi, ki, sm_scale=sm_scale, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 seq_len_k=seq_len_k, window=window,
                                 causal_shift=causal_shift,
                                 qseg=qs_ref[0] if has_seg else None,
                                 kseg=ks_ref[0] if has_seg else None)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = _block_live(qi, ki, causal=causal, block_q=block_q,
                       block_k=block_k, window=window)
    if live is None:
        _compute()
    else:
        pl.when(live)(_compute)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, sm_scale, causal, block_q, block_k,
                num_q_blocks, num_q_steps, seq_len_k, window=None,
                causal_shift=0, has_seg=False):
    j = pl.program_id(2)                   # folded (group, q_block) index
    ki = pl.program_id(1)
    qi = j % num_q_blocks

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0]                   # [block_q, 1]
        delta = delta_ref[0]
        s, mask = _masked_scores(q, k, qi, ki, sm_scale=sm_scale, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 seq_len_k=seq_len_k, window=window,
                                 causal_shift=causal_shift,
                                 qseg=qs_ref[0] if has_seg else None,
                                 kseg=ks_ref[0] if has_seg else None)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = _block_live(qi, ki, causal=causal, block_q=block_q,
                       block_k=block_k, window=window)
    if live is None:
        _compute()
    else:
        pl.when(live)(_compute)

    @pl.when(j == num_q_steps - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pallas_flash_bwd_impl(q, k, v, out, lse, g, causal, block_q, block_k,
                           interpret, window=None, causal_shift=0,
                           segment_ids=None):
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    sm_scale = 1.0 / np.sqrt(d)

    qp, op, gp = (_pad_seq(a, block_q) for a in (q, out, g))
    kp, vp = _pad_seq(k, block_k), _pad_seq(v, block_k)

    sq_p, sk_p = qp.shape[1], kp.shape[1]
    q2, k2, v2 = _fold(qp), _fold(kp), _fold(vp)
    do2, o2 = _fold(gp), _fold(op)
    qs, ks, has_seg = _seg_operands(segment_ids, sq, sk, block_q, block_k)
    delta = jnp.sum(do2.astype(jnp.float32) * o2.astype(jnp.float32),
                    axis=-1, keepdims=True)

    nq, nk = sq_p // block_q, sk_p // block_k

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          seq_len_k=sk, window=window,
                          causal_shift=causal_shift, has_seg=has_seg),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j, rep=rep: (bh // rep, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j, rep=rep: (bh // rep, j, 0)),
        ] + _seg_specs(has_seg, lambda bh, h=h: bh // h, block_q, block_k) + [
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q2, k2, v2, qs, ks, do2, lse, delta)

    # dKV: GQA group folded into the innermost grid axis → in-kernel accumulation
    nsteps = nq * rep
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          num_q_steps=nsteps, seq_len_k=sk, window=window,
                          causal_shift=causal_shift, has_seg=has_seg),
        grid=(b * hkv, nk, nsteps),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda bh, i, j, rep=rep, nq=nq:
                         (bh * rep + j // nq, j % nq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
        ] + ([
            # seg operands: q block j%nq (batch = bh // hkv), k block i
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, i, j, hkv=hkv, nq=nq:
                         (bh // hkv, j % nq, 0)),
            pl.BlockSpec((1, block_k, 1),
                         lambda bh, i, j, hkv=hkv: (bh // hkv, i, 0)),
        ] if has_seg else [
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (0, 0, 0)),
            pl.BlockSpec((1, block_k, 1), lambda bh, i, j: (0, 0, 0)),
        ]) + [
            pl.BlockSpec((1, block_q, d),
                         lambda bh, i, j, rep=rep, nq=nq:
                         (bh * rep + j // nq, j % nq, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, i, j, rep=rep, nq=nq:
                         (bh * rep + j // nq, j % nq, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, i, j, rep=rep, nq=nq:
                         (bh * rep + j // nq, j % nq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, sk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, sk_p, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q2, k2, v2, qs, ks, do2, lse, delta)

    return (_unfold(dq, b, h, sq), _unfold(dk, b, hkv, sk),
            _unfold(dv, b, hkv, sk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def pallas_flash_attention(q, k, v, causal: bool = True, block_q: int = 256,
                           block_k: int = 256, interpret: bool = False,
                           window=None, segment_ids=None):
    """Flash attention with Pallas forward and backward kernels.
    ``interpret=True`` runs the kernels in interpreter mode (CPU CI);
    ``window`` adds mistral-style sliding-window masking with below-window
    block skipping (long-context windowed cost is O(S*window));
    ``segment_ids`` [B, S] masks packed sequences in-kernel (tokens attend
    within their segment only)."""
    out, _ = _pallas_flash_fwd_impl(q, k, v, causal, block_q, block_k,
                                    interpret, window,
                                    segment_ids=segment_ids)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret, window, segment_ids):
    out, lse = _pallas_flash_fwd_impl(q, k, v, causal, block_q, block_k,
                                      interpret, window,
                                      segment_ids=segment_ids)
    return out, (q, k, v, out, lse, segment_ids)


def _bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse, segment_ids = res
    dq, dk, dv = _pallas_flash_bwd_impl(q, k, v, out, lse, g, causal, block_q,
                                        block_k, interpret, window,
                                        segment_ids=segment_ids)
    return dq, dk, dv, None


pallas_flash_attention.defvjp(_fwd, _bwd)


def flash_attention_auto(q, k, v, causal: bool = True, window=None,
                         segment_ids=None):
    """Dispatch: Pallas kernel on TPU, interpret/blockwise elsewhere."""
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        # bigger blocks amortize grid overhead (measured on v5e at s=2048,
        # d=128: fwd+bwd 10.9ms @256 / 4.9ms @512 / 4.6ms @1024); 1024-blocks
        # fit VMEM up to d=128 (acc scratch 1024*128*4B = 0.5MB per buffer)
        d = q.shape[-1]
        for blk in ((1024, 512, 256) if d <= 128 else (512, 256)):
            if q.shape[1] % blk == 0 and k.shape[1] % blk == 0:
                return pallas_flash_attention(q, k, v, causal, blk, blk,
                                              False, window, segment_ids)
        return pallas_flash_attention(q, k, v, causal, 256, 256, False,
                                      window, segment_ids)
    if window is not None or segment_ids is not None:
        from deepspeed_tpu.ops.flash_attention import attention_reference
        return attention_reference(q, k, v, causal=causal, window=window,
                                   segment_ids=segment_ids)
    return blockwise_reference(q, k, v, causal=causal)
