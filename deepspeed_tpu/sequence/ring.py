"""Ring attention — blockwise context parallelism over a ``ppermute`` ring.

The reference has NO ring attention (SURVEY.md §2.2: Ulysses all-to-all is its only
long-context mechanism); this is the TPU-side improvement called out in the survey:
KV blocks rotate around the ``sequence`` mesh axis while each device's queries stay
put, with flash-style online-softmax accumulation — O(S/P) activation memory and
communication that overlaps with the per-block attention compute (XLA pipelines the
``ppermute`` with the einsums).

Causality is handled with *global* positions: device i holds queries
[i*S_l, (i+1)*S_l); at ring step t it holds the KV block originating on device
(i - t) mod P, masked by qpos >= kpos.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.ops.flash_attention import NEG_INF, _repeat_kv




def ring_attention_local(q_l, k_l, v_l, sp: int, causal: bool = True,
                         axis_name: str = "sequence"):
    """The per-device ring body — callable from any shard_map whose manual
    axes include ``axis_name`` (ring_attention below, and the Ulysses
    uneven-heads remainder path in ``ulysses.py``). q_l: [B, S_l, H_l, D]
    local shards; returns [B, S_l, H_l, D]."""
    b, s_l, h_l, d = q_l.shape
    k_l, v_l = _repeat_kv(k_l, v_l, h_l)
    idx = jax.lax.axis_index(axis_name)
    qpos = idx * s_l + jnp.arange(s_l)
    scale = 1.0 / np.sqrt(d)
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def step(carry, t):
        k_cur, v_cur, m, l, o = carry
        src = (idx - t) % sp
        kpos = src * s_l + jnp.arange(s_l)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_l, k_cur,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = (qpos[:, None] >= kpos[None, :])[None, None]
            s = jnp.where(mask, s, NEG_INF)
        else:
            mask = jnp.bool_(True)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_cur.dtype), v_cur,
            preferred_element_type=jnp.float32)
        # rotate KV one hop around the ring (overlaps with next step's compute)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, m_new, l_new, o_new), None

    m0 = jnp.full((b, h_l, s_l), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h_l, s_l), jnp.float32)
    o0 = jnp.zeros((b, h_l, s_l, d), jnp.float32)
    (_, _, m, l, o), _ = jax.lax.scan(step, (k_l, v_l, m0, l0, o0),
                                      jnp.arange(sp))
    out = o / jnp.maximum(l, 1e-30)[..., None]          # [B, H, S_l, D]
    return out.transpose(0, 2, 1, 3).astype(q_l.dtype)  # [B, S_l, H, D]


# ---------------------------------------------------------------------------
# flash-kernel ring: the per-step [S_l, S_l] score panel never materializes
# ---------------------------------------------------------------------------

_SKIP_LSE = -1e30     # finite "no contribution" lse (a true -inf NaNs combine)


def _combine(o1, lse1, o2, lse2):
    """Merge two normalized partial attentions (o [B,S,H,D] f32,
    lse [B,H,S]) — the flash multi-block stitch."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    lse = m + jnp.log(w1 + w2)
    w1q = w1.transpose(0, 2, 1)[..., None]     # [B,S,H,1]
    w2q = w2.transpose(0, 2, 1)[..., None]
    o = (w1q * o1 + w2q * o2) / (w1q + w2q)
    return o, lse


def _ring_blocks(s_l: int):
    blk = 256
    while blk > s_l and blk > 8:
        blk //= 2
    return blk


# Striped layout (load balance): contiguous causal ring is skewed — device i
# computes i+1 live blocks of P, so the last device works every step while
# the first idles. With positions striped at stride P (device i holds global
# positions ≡ i mod P in blocks of S_l/P), qpos = m_q*P + i and
# kpos = m_k*P + src, so the causal test reduces to LOCAL causal with a
# one-row shift: m_q >= m_k + (1 if src > idx else 0) — every ring step on
# every device is one (shifted-)causal flash block of identical cost, and
# the kernel's diagonal skipping drops the dead half. Resharding is one
# all_to_all each way, which JAX differentiates through (its transpose is
# the inverse all_to_all).


def _stripe(x, sp, axis_name):
    """Contiguous seq shard -> striped shard (positions ≡ idx mod sp)."""
    b, s_l = x.shape[:2]
    y = x.reshape(b, s_l // sp, sp, *x.shape[2:])
    y = jax.lax.all_to_all(y, axis_name, split_axis=2, concat_axis=2)
    return jnp.swapaxes(y, 1, 2).reshape(x.shape)


def _unstripe(x, sp, axis_name):
    b, s_l = x.shape[:2]
    y = x.reshape(b, sp, s_l // sp, *x.shape[2:])
    y = jax.lax.all_to_all(y, axis_name, split_axis=1, concat_axis=1)
    return jnp.swapaxes(y, 1, 2).reshape(x.shape)


# One fwd/bwd scaffold serves both ring layouts; ``mode`` picks the
# per-step block policy (static, hashable -> one trace per mode):
#   "causal":  contiguous layout — diagonal step causal, earlier steps full,
#              later steps skipped (the skew the striped layout removes)
#   "full":    non-causal — every step a full block
#   "striped": striped layout — every step causal, with a one-row shift on
#              strictly-future stripes (src > idx)


def _step_fwd(mode, src, idx, block, skip):
    """block(causal, shift) -> (o, lse); skip() -> zero contribution."""
    if mode == "full":
        return block(False, 0)
    if mode == "striped":
        return jax.lax.cond(src > idx,
                            lambda: block(True, 1), lambda: block(True, 0))
    return jax.lax.cond(
        src == idx, lambda: block(True, 0),
        lambda: jax.lax.cond(src < idx, lambda: block(False, 0), skip))


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring_core(q_l, k_l, v_l, seg_l, sp: int, mode: str, axis_name: str,
               interpret: bool):
    """Ring attention whose per-step block attention is the Pallas flash
    kernel: fwd stitches the blocks' (o, lse) online; bwd re-rotates KV and
    runs the flash backward per block against the FINAL lse (the standard
    multi-block decomposition — per-block probabilities under the global
    softmax), with dk/dv accumulators riding the ring home. q_l [B,S_l,H,D],
    k_l/v_l [B,S_l,Hkv,D] (GQA handled inside the kernel). ``seg_l``
    [B, S_l] packed-sequence ids or None; the KV block's ids ride the ring
    with it (local queries keep their own)."""
    out, _ = _ring_fwd(q_l, k_l, v_l, seg_l, sp, mode, axis_name, interpret)
    return out


def _ring_fwd(q_l, k_l, v_l, seg_l, sp, mode, axis_name, interpret):
    from deepspeed_tpu.ops.pallas.flash_attention import _pallas_flash_fwd_impl
    b, s_l, h, d = q_l.shape
    blk = _ring_blocks(s_l)
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % sp) for j in range(sp)]
    has_seg = seg_l is not None
    kseg0 = seg_l if has_seg else jnp.zeros((b, s_l), jnp.int32)

    def step(carry, t):
        k_cur, v_cur, kseg_cur, o_acc, lse_acc = carry
        src = (idx - t) % sp

        def block(kv_causal, shift):
            o, lse = _pallas_flash_fwd_impl(
                q_l, k_cur, v_cur, kv_causal, blk, blk, interpret, None,
                causal_shift=shift,
                segment_ids=(seg_l, kseg_cur) if has_seg else None)
            return (o.astype(jnp.float32),
                    lse[:, 0, :s_l].reshape(b, h, s_l))

        def skip():
            return (jnp.zeros((b, s_l, h, d), jnp.float32),
                    jnp.full((b, h, s_l), _SKIP_LSE, jnp.float32))

        o_t, lse_t = _step_fwd(mode, src, idx, block, skip)
        o_acc, lse_acc = _combine(o_acc, lse_acc, o_t, lse_t)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        kseg_next = jax.lax.ppermute(kseg_cur, axis_name, perm)
        return (k_next, v_next, kseg_next, o_acc, lse_acc), None

    o0 = jnp.zeros((b, s_l, h, d), jnp.float32)
    lse0 = jnp.full((b, h, s_l), _SKIP_LSE, jnp.float32)
    (_, _, _, o, lse), _ = jax.lax.scan(step, (k_l, v_l, kseg0, o0, lse0),
                                        jnp.arange(sp))
    return o.astype(q_l.dtype), lse


def _ring_fwd_vjp(q_l, k_l, v_l, seg_l, sp, mode, axis_name, interpret):
    out, lse = _ring_fwd(q_l, k_l, v_l, seg_l, sp, mode, axis_name, interpret)
    return out, (q_l, k_l, v_l, seg_l, out, lse)


def _ring_bwd(sp, mode, axis_name, interpret, res, g):
    from deepspeed_tpu.ops.pallas.flash_attention import _pallas_flash_bwd_impl
    q_l, k_l, v_l, seg_l, out, lse = res
    b, s_l, h, d = q_l.shape
    blk = _ring_blocks(s_l)
    # the bwd impl consumes lse in its folded padded layout [B*H, 1, S_pad]
    lse_f = jnp.pad(lse.reshape(b * h, 1, s_l),
                    ((0, 0), (0, 0), (0, (-s_l) % blk)))
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % sp) for j in range(sp)]
    has_seg = seg_l is not None
    b2 = q_l.shape[0]
    kseg0 = seg_l if has_seg else jnp.zeros((b2, s_l), jnp.int32)

    def step(carry, t):
        k_cur, v_cur, kseg_cur, dk_acc, dv_acc, dq_acc = carry
        src = (idx - t) % sp

        def block(kv_causal, shift):
            return _pallas_flash_bwd_impl(
                q_l, k_cur, v_cur, out, lse_f, g, kv_causal, blk, blk,
                interpret, None, causal_shift=shift,
                segment_ids=(seg_l, kseg_cur) if has_seg else None)

        def skip():
            return (jnp.zeros_like(q_l), jnp.zeros_like(k_cur),
                    jnp.zeros_like(v_cur))

        dq_c, dk_c, dv_c = _step_fwd(mode, src, idx, block, skip)
        dq_acc = dq_acc + dq_c.astype(jnp.float32)
        dk_acc = dk_acc + dk_c.astype(jnp.float32)
        dv_acc = dv_acc + dv_c.astype(jnp.float32)
        # dk/dv accumulators ride the ring WITH their block; after sp hops
        # every block (and its gradient) is back on its home device
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        kseg_next = jax.lax.ppermute(kseg_cur, axis_name, perm)
        dk_next = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_next = jax.lax.ppermute(dv_acc, axis_name, perm)
        return (k_next, v_next, kseg_next, dk_next, dv_next, dq_acc), None

    (_, _, _, dk, dv, dq), _ = jax.lax.scan(
        step, (k_l, v_l, kseg0, jnp.zeros(k_l.shape, jnp.float32),
               jnp.zeros(v_l.shape, jnp.float32),
               jnp.zeros(q_l.shape, jnp.float32)),
        jnp.arange(sp))
    return (dq.astype(q_l.dtype), dk.astype(k_l.dtype),
            dv.astype(v_l.dtype), None)


_ring_core.defvjp(_ring_fwd_vjp, _ring_bwd)


def ring_attention_local_flash(q_l, k_l, v_l, sp: int, causal: bool,
                               axis_name: str = "sequence",
                               interpret: bool = False, seg_l=None):
    """Contiguous-layout flash ring (see _ring_core)."""
    return _ring_core(q_l, k_l, v_l, seg_l, sp,
                      "causal" if causal else "full", axis_name, interpret)


def ring_attention_local_striped(q_l, k_l, v_l, sp: int,
                                 axis_name: str = "sequence",
                                 interpret: bool = False, seg_l=None):
    """Load-balanced causal ring: stripe q/k/v (and the segment ids), run
    the shifted-causal flash ring, unstripe the output. Requires
    S_l % sp == 0 (checked by caller)."""
    q_s = _stripe(q_l, sp, axis_name)
    k_s = _stripe(k_l, sp, axis_name)
    v_s = _stripe(v_l, sp, axis_name)
    seg_s = _stripe(seg_l, sp, axis_name) if seg_l is not None else None
    out = _ring_core(q_s, k_s, v_s, seg_s, sp, "striped", axis_name,
                     interpret)
    return _unstripe(out, sp, axis_name)


def ring_attention(q, k, v, causal: bool = True, mesh=None,
                   impl: Optional[str] = None, segment_ids=None):
    """q,k,v: [B, S, H(kv), D] global, sequence-sharded. Returns [B, S, H, D].

    ``impl``: ``"flash"`` (Pallas kernel per ring block — O(block) memory,
    MXU-tiled; causal runs STRIPED for load balance when S_l % sp == 0;
    TPU default), ``"flash_contiguous"`` (skew-causal flash ring, no
    resharding), ``"xla"`` (the jnp online-softmax body — any backend),
    ``"interpret"`` / ``"interpret_contiguous"`` (the flash paths in
    interpreter mode, for CPU tests). Default picks flash on TPU, xla
    elsewhere.
    """
    mesh = mesh or mesh_lib.get_global_mesh()
    sp = mesh.shape["sequence"]
    if sp == 1:
        from deepspeed_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal,
                               segment_ids=segment_ids)
    if impl is None:
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    if segment_ids is not None and impl == "xla":
        raise NotImplementedError(
            "packed-sequence segment_ids need the flash ring (the jnp body "
            "does not carry segment ids) — impl='flash' or 'interpret'")

    spec_q = P(mesh_lib.batch_axes(mesh), "sequence", "tensor", None)
    seg_spec = P(mesh_lib.batch_axes(mesh), "sequence")
    s_l = q.shape[1] // sp
    striped = causal and s_l % sp == 0 and impl in ("flash", "interpret")

    if impl == "xla":
        def body(q_l, k_l, v_l, seg_l=None):
            return ring_attention_local(q_l, k_l, v_l, sp, causal=causal)
    elif striped:
        interpret = impl == "interpret"

        def body(q_l, k_l, v_l, seg_l=None):
            return ring_attention_local_striped(q_l, k_l, v_l, sp,
                                                "sequence", interpret,
                                                seg_l=seg_l)
    else:
        interpret = impl.startswith("interpret")

        def body(q_l, k_l, v_l, seg_l=None):
            return ring_attention_local_flash(q_l, k_l, v_l, sp, causal,
                                              "sequence", interpret,
                                              seg_l=seg_l)

    if segment_ids is not None:
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec_q, spec_q, spec_q, seg_spec),
            out_specs=spec_q, check_vma=False)(
                q, k, v, jnp.asarray(segment_ids, jnp.int32))
    return jax.shard_map(body, mesh=mesh, in_specs=(spec_q, spec_q, spec_q),
                         out_specs=spec_q, check_vma=False)(q, k, v)
