"""Pallas flash-attention kernels (forward + backward).

Reference analog: the fused attention CUDA kernels
(``csrc/transformer/inference/csrc/softmax.cu``, the training transformer kernel
suite ``csrc/transformer/`` fused fwd+bwd, v2 ``blocked_flash``). TPU design:
canonical sequential-grid flash — grid (batch*heads, q_blocks, k_steps) with the
k dimension innermost (TPU grids execute sequentially, so VMEM scratch accumulators
carry across k steps): online-softmax max/sum/output accumulators in fp32 scratch,
[block_q, block_k] score panels on the MXU, GQA handled by index-mapping q heads
onto shared KV heads (no KV repeat materialized).

The panel schedule (``_Schedule``) is a function of the call's static shapes
and mask arguments alone, and says which panels a grid visits and which body
each one gets:

- only the causal band is a grid step. With a sliding ``window`` the innermost
  axis is as long as the most panels any block's band crosses and the index
  maps add the block's first live panel; a step past a block's last live panel
  names that panel again, which the pipeline does not fetch twice, and computes
  nothing (the reference's fused kernels get the same effect from their
  triangular launch bounds). A non-causal call has no band and keeps its grid;
- a live panel runs one of two bodies. One that lies wholly inside the band
  (under the diagonal, above the window's lower edge, no padded keys, no
  segment ids) builds no mask; one that the diagonal, the window's edge or the
  padded tail crosses runs the masked body. ``where(True, x, ..)`` is ``x``:
  on a panel both may take they give the same values bit for bit;
- row statistics never sit one lane a row. The forward holds its running
  maximum and sum ``[block_q, 128]`` (every lane the row's value, the step
  ``latent_attention`` shares); ``lse`` and ``delta`` live in HBM as rows
  ``[B*H, 1, S]`` (a ``[.., S, 1]`` float32 column is tiled (8, 128) there:
  128 times its bytes, and a block of it as large a fetch as a key block).
  The dQ kernel spreads its block's two rows over the lanes once a row block;
  the dKV kernel works on the transposed panel ``K Q^T`` ``[block_k,
  block_q]``, where a row broadcasts over sublanes and both accumulating
  products are plain ``A @ B``.

Backward: FlashAttention-2 style two-kernel recompute. The forward additionally
emits the per-row logsumexp; backward precomputes ``delta = rowsum(dO * O)``
with XLA, then
- a dQ kernel over grid (B*H, q_blocks, k_steps) accumulating
  ``dq += ds @ K`` in fp32 VMEM scratch, and
- a dKV kernel over grid (B*Hkv, k_blocks, group * q_steps) accumulating
  ``dk += ds^T @ Q`` / ``dv += p^T @ dO`` — the GQA group dimension is folded
  into the innermost grid axis so gradients for KV heads shared by several query
  heads accumulate in-kernel (no rep-times-larger intermediate in HBM).

On a TPU ``block_q`` and ``block_k`` are multiples of 128 (or the whole padded
length): the rows of ``lse`` are cut into blocks along the lanes.

What each of that is worth was timed on a v5e, the kernels alone, at 8,192
rows under a 4,096 window and at 2,048 rows without one, 32 query heads over
8 KV heads of 128 (PERF.md section 6, PR 50, has every candidate). At the
first shape, forward / dQ / dKV in ms a call: the form before (one-lane
columns, every panel masked, every dead panel a grid step with fresh blocks,
``lse`` and ``delta`` as ``[.., S, 1]`` columns, 1,024 x 1,024 panels) 4.96 /
5.77 / 8.02; this form at the same panel 4.22 / 4.87 / 5.98, of which the
band is 2% / 4% / 10%, the unmasked body 10% / 9% / 4% and the rest the rows
and the transposed dKV panel; at the panels ``_auto_panel`` chooses (the
forward 512 x 512, where lane-dense statistics are 39% of a call and 0.8% at
1,024; the backward 1,024 x 1,024) 3.81 / 4.87 / 5.98. Inside a computed
panel the backward kernels' products run at 81% and 88% of the bf16 peak and
the forward's at 62%, bound by the vector unit's passes over the float32
panel. Timed and dropped: ``sm_scale`` applied to the accumulated ``dq`` and
``dk`` instead of every ``ds`` panel (dQ 3.6% faster, dKV level, ``ds``
rounded differently).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.flash_attention import flash_attention as blockwise_reference
from deepspeed_tpu.ops.pallas.latent_attention import (
    _LANES, _across, _init_scratch, _online_softmax_step)


def _at_most(x, hi):
    return jnp.minimum(x, hi) if isinstance(x, jax.Array) else min(x, hi)


def _at_least(x, lo):
    return jnp.maximum(x, lo) if isinstance(x, jax.Array) else max(x, lo)


class _Schedule(NamedTuple):
    """The panels of one head's ``[nq, nk]`` grid of ``[block_q, block_k]``
    panels under the call's mask: kv padding past ``seq_len_k``, the causal
    band ``qpos >= kpos + shift`` (``banded``; ``shift`` 1 is the ring's
    STRICT band) and mistral-style ``window``: token t sees (t-window, t].
    Every method takes plain ints (``panels``, the tests) or the traced
    indices of a grid step (the kernels and their index maps)."""
    block_q: int
    block_k: int
    nq: int
    nk: int
    seq_len_k: int
    banded: bool
    window: Optional[int]
    shift: int
    has_seg: bool

    # -- a row block's band of key panels: the forward and dQ grids ---------
    def first_k(self, qi):
        if self.window is None:
            return 0
        return _at_least(qi * self.block_q - self.window + 1, 0) // self.block_k

    def last_k(self, qi):
        if not self.banded:
            return self.nk - 1
        last_row = qi * self.block_q + self.block_q - 1
        return _at_most(_at_least(last_row - self.shift, 0) // self.block_k,
                        self.nk - 1)

    @property
    def k_steps(self) -> int:
        """Length of the innermost axis: the most panels a band crosses."""
        return max(1, 1 + max(self.last_k(i) - self.first_k(i)
                              for i in range(self.nq)))

    def k_block(self, qi, j):
        """The key panel step ``j`` of row block ``qi`` names: past the
        band's end the last live one again, which is not fetched twice."""
        return _at_most(self.first_k(qi) + j, self.last_k(qi))

    # -- a key block's band of row blocks: the dKV grid ----------------------
    def first_q(self, ki):
        if not self.banded:
            return 0
        return _at_most((ki * self.block_k + self.shift) // self.block_q,
                        self.nq - 1)

    def last_q(self, ki):
        if self.window is None:
            return self.nq - 1
        last_key = ki * self.block_k + self.block_k - 1
        return _at_most((last_key + self.window - 1) // self.block_q,
                        self.nq - 1)

    @property
    def q_steps(self) -> int:
        return max(1, 1 + max(self.last_q(i) - self.first_q(i)
                              for i in range(self.nk)))

    def q_block(self, ki, t):
        return _at_most(self.first_q(ki) + t, self.last_q(ki))

    def kind(self, qi, ki):
        """``(live, whole)`` of panel ``(qi, ki)``: some row sees some key;
        every row sees every key, so the panel needs no mask. ``ki`` and
        ``qi`` may lie past a band's end (a surplus step): not live."""
        bq, bk = self.block_q, self.block_k
        live = whole = True
        if self.banded:
            live = ((ki < self.nk) & (qi < self.nq)
                    & (ki * bk + self.shift <= qi * bq + bq - 1))
            whole = live & (ki * bk + bk - 1 + self.shift <= qi * bq)
        if self.window is not None:
            last_key = _at_most(ki * bk + bk - 1, self.seq_len_k - 1)
            live = live & (last_key > qi * bq - self.window)
            whole = whole & (ki * bk > qi * bq + bq - 1 - self.window)
        if self.seq_len_k % bk:
            whole = whole & ((ki + 1) * bk <= self.seq_len_k)
        # segment ids are data: every panel of such a call is masked
        return live, (False if self.has_seg else whole)


def _schedule(sq, sk, block_q, block_k, causal, window, causal_shift,
              has_seg) -> _Schedule:
    # a window implies the causal band (t-window, t] — same contract as
    # attention_reference/_xla_attention
    return _Schedule(block_q, block_k, -(-sq // block_q), -(-sk // block_k),
                     sk, bool(causal) or window is not None, window,
                     causal_shift, has_seg)


def _when(cond, body):
    return body() if cond is True else pl.when(cond)(body)


def _panel_bodies(live, whole, step):
    """``step(masked)`` for a live panel: the unmasked body where it is
    ``whole``, the masked one where something crosses it, neither on a
    dead step. A kind the call's static arguments decide emits one body."""
    if whole is False:
        return _when(live, lambda: step(True))
    _when(whole, lambda: step(False))
    if whole is not True:
        _when(live & jnp.logical_not(whole), lambda: step(True))


def _nt(a, b):
    """``a @ b^T`` in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mask(shape, q_axis, qi, ki, sched, qseg, kseg):
    """The mask of panel ``(qi, ki)`` with the rows along ``q_axis`` — shared
    by the forward and both backward kernels, which keeps their masking
    locked together: kv padding AND the causal band AND the window AND
    (packed sequences) tokens attend within their segment only. ``qseg`` and
    ``kseg`` broadcast against each other, one a column and one a row."""
    qpos = qi * sched.block_q + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                         q_axis)
    kpos = ki * sched.block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                         1 - q_axis)
    mask = kpos < sched.seq_len_k
    if sched.banded:
        mask = jnp.logical_and(mask, qpos >= kpos + sched.shift)
    if sched.window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - sched.window)
    if sched.has_seg:
        mask = jnp.logical_and(mask, qseg == kseg)
    return mask


def _row(dense):
    """Lane-dense ``[rows, 128]`` statistics as one row ``[1, rows]``."""
    return dense.T[:1]


def _lanes(row):
    """A row ``[1, rows]`` spread lane-dense ``[rows, 128]``."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _flash_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr, *, sm_scale, sched):
    qi, j = pl.program_id(1), pl.program_id(2)
    ki = sched.first_k(qi) + j

    pl.when(j == 0)(lambda: _init_scratch(m_scr, l_scr, acc_scr))

    def _step(masked):
        s = _nt(q_ref[0], k_ref[0]) * sm_scale        # [block_q, block_k]
        mask = _mask(s.shape, 0, qi, ki, sched, qs_ref[0], ks_ref[0]) \
            if masked else None
        _online_softmax_step(s, mask, v_ref[0], m_scr, l_scr, acc_scr)

    _panel_bodies(*sched.kind(qi, ki), _step)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / _across(l, acc_scr.shape[1])
                    ).astype(o_ref.dtype)
        lse_ref[0] = _row(m_scr[:] + jnp.log(l))


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
    """Padded int32 segment arrays ``(q_ids [B, Sq_p], k_ids [B, Sk_p],
    has_seg)``. ``segment_ids`` is [B, S] shared by q and k, or a ``(q_ids
    [B, Sq], k_ids [B, Sk])`` pair (ring attention: the rotating KV block
    carries different ids than the local queries). Padding uses -1 on the k
    side so padded keys mismatch every real segment (they are also masked by
    seq_len_k). A kernel takes the one along its panel's sublanes as a
    column ``[B, S, 1]`` and the other as a row ``[B, 1, S]``."""
    if segment_ids is None:
        return (jnp.zeros((1, block_q), jnp.int32),
                jnp.zeros((1, block_k), jnp.int32), False)
    if isinstance(segment_ids, tuple):
        q_ids, k_ids = segment_ids
    else:
        q_ids = k_ids = segment_ids
    qs = jnp.pad(jnp.asarray(q_ids, jnp.int32),
                 ((0, 0), (0, (-sq) % block_q)), constant_values=-1)
    ks = jnp.pad(jnp.asarray(k_ids, jnp.int32)[:, :sk],
                 ((0, 0), (0, (-sk) % block_k)), constant_values=-1)
    return qs, ks, True


def _seg_specs(has_seg, batch_of, col_block, col_of, row_block, row_of):
    """Block specs of the segment operands ``(column [B, S, 1], row
    [B, 1, S])``, indexed by BATCH (``batch_of(grid dim 0)``) and by the
    panel's block along each (``col_of`` / ``row_of`` of the two inner grid
    indices); the placeholders of a call without segment ids stay put."""
    if not has_seg:
        return [pl.BlockSpec((1, col_block, 1), lambda g, i, j: (0, 0, 0)),
                pl.BlockSpec((1, 1, row_block), lambda g, i, j: (0, 0, 0))]
    return [pl.BlockSpec((1, col_block, 1),
                         lambda g, i, j: (batch_of(g), col_of(i, j), 0)),
            pl.BlockSpec((1, 1, row_block),
                         lambda g, i, j: (batch_of(g), 0, row_of(i, j)))]


def _pallas_flash_fwd_impl(q, k, v, causal: bool, block_q: int, block_k: int,
                           interpret: bool, window=None, causal_shift=0,
                           segment_ids=None):
    """q: [B, Sq, H, D]; k,v: [B, Sk, Hkv, D] -> (out, lse[B*H, 1, Sq_padded])."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    sm_scale = 1.0 / np.sqrt(d)

    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v, block_k)
    sq_p = qp.shape[1]
    q2, k2, v2 = _fold(qp), _fold(kp), _fold(vp)
    qs, ks, has_seg = _seg_operands(segment_ids, sq, sk, block_q, block_k)
    sched = _schedule(sq, sk, block_q, block_k, causal, window, causal_shift,
                      has_seg)
    rows = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    keys = pl.BlockSpec((1, block_k, d), lambda bh, i, j:
                        (bh // rep, sched.k_block(i, j), 0))

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=sm_scale, sched=sched),
        grid=(b * h, sched.nq, sched.k_steps),
        in_specs=[rows, keys, keys] + _seg_specs(
            has_seg, lambda bh: bh // h, block_q, lambda i, j: i,
            block_k, sched.k_block),
        out_specs=[
            rows,
            # a row of the padded length: lane-tiled in HBM, where a
            # [.., S, 1] column is tiled (8, 128), 128 times its bytes
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q2, k2, v2, qs[..., None], ks[:, None, :])

    return _unfold(out, b, h, sq), lse


def _dq_panel(q, k, v, do, lse, delta, mask, sm_scale):
    """One panel's share of dQ ``[block_q, D]`` in float32. ``lse`` and
    ``delta``: the row block's, ``[block_q, 1]`` or lane-dense ``[block_q,
    128]``. ``mask`` None is a panel every row sees whole: no ``where``, and
    the same values bit for bit as an all-true mask gives."""
    s = _nt(q, k) * sm_scale                          # [block_q, block_k]
    p = jnp.exp(s - _across(lse, s.shape[1]))
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    ds = p * (_nt(do, v) - _across(delta, s.shape[1])) * sm_scale
    return jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)


def _dkv_panel(q, k, v, do, lse, delta, mask, sm_scale):
    """One panel's shares ``(dK, dV)`` ``[block_k, D]`` in float32, on the
    transposed panel ``K Q^T`` ``[block_k, block_q]``: ``lse`` and ``delta``
    are rows ``[1, block_q]``, which broadcast over sublanes, and both
    products are plain ``A @ B``. ``mask`` (rows along axis 1) as in
    ``_dq_panel``."""
    st = _nt(k, q) * sm_scale
    pt = jnp.exp(st - lse)
    if mask is not None:
        pt = jnp.where(mask, pt, 0.0)
    dv = jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
    dst = pt * (_nt(v, do) - delta) * sm_scale
    return jnp.dot(dst.astype(q.dtype), q,
                   preferred_element_type=jnp.float32), dv


def _dq_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, lse_scr, delta_scr, *,
               sm_scale, sched):
    qi, j = pl.program_id(1), pl.program_id(2)
    ki = sched.first_k(qi) + j

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # the row block's lse and delta, lane-dense once for all its panels
        lse_scr[:] = _lanes(lse_ref[0])
        delta_scr[:] = _lanes(delta_ref[0])

    def _step(masked):
        mask = _mask((sched.block_q, sched.block_k), 0, qi, ki, sched,
                     qs_ref[0], ks_ref[0]) if masked else None
        dq_scr[:] = dq_scr[:] + _dq_panel(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_scr[:],
            delta_scr[:], mask, sm_scale)

    _panel_bodies(*sched.kind(qi, ki), _step)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, ks_ref, qs_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale,
                sched):
    ki, j = pl.program_id(1), pl.program_id(2)   # j: folded (group, q step)
    qi = sched.first_q(ki) + j % sched.q_steps

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step(masked):
        mask = _mask((sched.block_k, sched.block_q), 1, qi, ki, sched,
                     qs_ref[0], ks_ref[0]) if masked else None
        dk, dv = _dkv_panel(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                            lse_ref[0], delta_ref[0], mask, sm_scale)
        dk_scr[:] = dk_scr[:] + dk
        dv_scr[:] = dv_scr[:] + dv

    _panel_bodies(*sched.kind(qi, ki), _step)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pallas_flash_bwd_impl(q, k, v, out, lse, g, causal, block_q, block_k,
                           interpret, window=None, causal_shift=0,
                           segment_ids=None):
    """``lse``: [B*H, 1, Sq_padded] as the forward returns it."""
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
    sched = _schedule(sq, sk, block_q, block_k, causal, window, causal_shift,
                      has_seg)
    delta = jnp.sum(do2.astype(jnp.float32) * o2.astype(jnp.float32),
                    axis=-1)[:, None, :]

    rows = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    keys = pl.BlockSpec((1, block_k, d), lambda bh, i, j:
                        (bh // rep, sched.k_block(i, j), 0))
    stat = pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, sched=sched),
        grid=(b * h, sched.nq, sched.k_steps),
        in_specs=[rows, keys, keys] + _seg_specs(
            has_seg, lambda bh: bh // h, block_q, lambda i, j: i,
            block_k, sched.k_block) + [rows, stat, stat],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q2, k2, v2, qs[..., None], ks[:, None, :], do2, lse, delta)

    # dKV: the GQA group folded into the innermost grid axis → in-kernel
    # accumulation; step j is head j // q_steps of the group at the key
    # block's q step j % q_steps
    steps = sched.q_steps

    def q_of(i, j):
        return sched.q_block(i, j % steps)

    rows = pl.BlockSpec((1, block_q, d), lambda bh, i, j:
                        (bh * rep + j // steps, q_of(i, j), 0))
    keys = pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0))
    stat = pl.BlockSpec((1, 1, block_q), lambda bh, i, j:
                        (bh * rep + j // steps, 0, q_of(i, j)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, sched=sched),
        grid=(b * hkv, sched.nk, rep * steps),
        in_specs=[rows, keys, keys] + _seg_specs(
            has_seg, lambda bh: bh // hkv, block_k, lambda i, j: i,
            block_q, q_of) + [rows, stat, stat],
        out_specs=[keys, keys],
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
    )(q2, k2, v2, ks[..., None], qs[:, None, :], do2, lse, delta)

    return (_unfold(dq, b, h, sq), _unfold(dk, b, hkv, sk),
            _unfold(dv, b, hkv, sk))


#: A side of the square panel each kernel cuts, at most: the kernel-alone sweep
#: on a v5e (PERF.md section 6, PR 50; 8,192 rows under a 4,096 window and
#: 2,048 rows without one agree). The forward wastes less on the diagonal and
#: the window's edge at 512 and, its statistics lane-dense, pays nothing for
#: the extra steps; the backward kernels run their panels' matmuls back to
#: back (81% and 88% of the peak inside a panel) and want the fewest steps.
_PANEL_SIDE = {"fwd": 512, "bwd": 1024}


def _auto_panel(sq: int, sk: int, head_dim: int, kernel: str = "fwd"):
    """``(block_q, block_k)`` of ``kernel`` (``"fwd"``, or ``"bwd"`` for dQ
    and dKV) for a call of ``sq`` rows over ``sk`` keys at ``head_dim``, from
    those static sizes alone: the largest side up to the kernel's own that
    divides the length (above d=128 up to 512: a float32 ``[1024, 1024]``
    panel is 4 MB a temporary, and 2,048 x 1,024 already passes the scoped
    VMEM at 128); a length that none divides is padded to 256, by every
    kernel alike, so the forward's ``lse`` is as long as the backward's."""
    most = min(_PANEL_SIDE[kernel], 1024 if head_dim <= 128 else 512)

    def side(n):
        return next((c for c in (1024, 512, 256)
                     if c <= most and n % c == 0), 256)
    return side(sq), side(sk)


def _panel_of(q, k, block_q, block_k, kernel):
    if block_q is None:
        return _auto_panel(q.shape[1], k.shape[1], q.shape[-1], kernel)
    return block_q, block_k


def panels(sq: int, sk: int, causal: bool = True, window=None,
           head_dim: int = 128, causal_shift: int = 0) -> dict:
    """What one head's forward grid is made of, at the panel
    ``flash_attention_auto`` would choose for the call: ``flash_panels`` that
    compute (some row sees some key), ``flash_panels_masked`` of them that the
    diagonal, the window's lower edge or the padded tail crosses (they build
    and apply the mask; the others lie wholly inside the band) and
    ``flash_steps_dead`` grid steps past a row block's band, which fetch and
    compute nothing. The kernels' own schedule, on plain ints."""
    sched = _schedule(sq, sk, *_auto_panel(sq, sk, head_dim), causal, window,
                      causal_shift, False)
    kinds = [sched.kind(qi, sched.first_k(qi) + j)
             for qi in range(sched.nq) for j in range(sched.k_steps)]
    live = sum(1 for is_live, _ in kinds if is_live)
    return {"flash_panels": live,
            "flash_panels_masked": live - sum(1 for _, whole in kinds
                                              if whole),
            "flash_steps_dead": len(kinds) - live}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def pallas_flash_attention(q, k, v, causal: bool = True, block_q=None,
                           block_k=None, interpret: bool = False,
                           window=None, segment_ids=None):
    """Flash attention with Pallas forward and backward kernels.
    ``block_q`` / ``block_k``: the panel of all three kernels, or None for
    each kernel's own from the call's static shapes (``_auto_panel``);
    ``interpret=True`` runs the kernels in interpreter mode (CPU CI);
    ``window`` adds mistral-style sliding-window masking, the grid cut to
    the band (long-context windowed cost is O(S*window));
    ``segment_ids`` [B, S] masks packed sequences in-kernel (tokens attend
    within their segment only)."""
    return _fwd(q, k, v, causal, block_q, block_k, interpret, window,
                segment_ids)[0]


def _fwd(q, k, v, causal, block_q, block_k, interpret, window, segment_ids):
    out, lse = _pallas_flash_fwd_impl(
        q, k, v, causal, *_panel_of(q, k, block_q, block_k, "fwd"),
        interpret, window, segment_ids=segment_ids)
    return out, (q, k, v, out, lse, segment_ids)


def _bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse, segment_ids = res
    dq, dk, dv = _pallas_flash_bwd_impl(
        q, k, v, out, lse, g, causal,
        *_panel_of(q, k, block_q, block_k, "bwd"), interpret, window,
        segment_ids=segment_ids)
    return dq, dk, dv, None


pallas_flash_attention.defvjp(_fwd, _bwd)


def flash_attention_auto(q, k, v, causal: bool = True, window=None,
                         segment_ids=None):
    """Dispatch: Pallas kernel on TPU, interpret/blockwise elsewhere."""
    if jax.default_backend() == "tpu":
        return pallas_flash_attention(q, k, v, causal, None, None, False,
                                      window, segment_ids)
    if window is not None or segment_ids is not None:
        from deepspeed_tpu.ops.flash_attention import attention_reference
        return attention_reference(q, k, v, causal=causal, window=window,
                                   segment_ids=segment_ids)
    return blockwise_reference(q, k, v, causal=causal)
