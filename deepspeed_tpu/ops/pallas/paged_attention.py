"""Pallas paged (blocked) attention over the serving KV cache.

Reference analog: ``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``
(flash attention over paged KV) + ``atom_builder`` (ragged batch splitting).

TPU design: the block table rides as a **scalar-prefetch** argument
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index map dereferences it
and the kernel DMAs each sequence's KV pages *directly out of the paged pool in
HBM* — the gather fallback's [B, MB*bs, H, d] context re-materialization (plus
rep-times KV expansion for GQA) never exists. Grid (batch, kv_head, page) with
the page dimension innermost: online-softmax accumulators live in VMEM scratch
and carry across pages, flash-style.

GQA/T folding: the kernel processes one KV head per grid cell; the q rows for
that cell are the (group × chunk) fold — ``rep`` query heads that share the KV
head times ``T`` chunk tokens — zero-padded to a multiple of 8 sublanes. Decode
is T=1; prefill is B=1, T=chunk. A fold too tall for the compiler's scoped VMEM
(a 2048-token chunk of a 4-way group is 8192 rows) is cut into row blocks on a
grid axis of their own (``_MAX_FOLD_ELEMS``). Pages entirely above a row
block's causal horizon (or entirely below its sliding window) are predicated
out with ``pl.when``.

Cache layout is head-major ``[Hkv, num_blocks, block_size, d]`` so one page of
one KV head is a contiguous ``(block_size, d)`` tile (legal TPU block shape).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Tallest q fold one grid cell takes, in rows x head_dim elements. The kernel
# sets no compiler parameters, so it lives inside the default 16 MiB of scoped
# VMEM: on a v5e (libtpu 0.0.34) 4096 rows x d128 still compiles and 8192 rows
# are refused at 28 MiB. Half the largest size that fits leaves the margin.
_MAX_FOLD_ELEMS = 2048 * 128


def _paged_kernel(*refs, block_size, num_pages, chunk, rows,
                  window, softcap, num_blocks=0):
    if num_blocks:      # fp8 pages with per-(head, page) scales prefetched
        (tables_ref, start_ref, kscale_ref, vscale_ref, q_ref, k_ref, v_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (tables_ref, start_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
         acc_scr) = refs
        kscale_ref = vscale_ref = None
    b = pl.program_id(0)
    hi = pl.program_id(1)
    i = pl.program_id(2)                   # row block of the q fold
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = start_ref[b]
    if chunk % rows == 0:
        # the row block is a run of consecutive tokens of one q head
        min_qpos = start + (i * rows) % chunk
        max_qpos = min_qpos + rows - 1
    else:                                  # it spans heads: the whole chunk
        min_qpos = start
        max_qpos = start + chunk - 1

    def _compute():
        q = q_ref[0, 0]                    # [Gp, d]
        k = k_ref[0, 0]                    # [bs, d] (fp8 pages dequantize
        v = v_ref[0, 0]                    # on load; no-op otherwise)
        if kscale_ref is not None:
            # per-(head, page) scale rides in SMEM next to the block table
            page = tables_ref[b * num_pages + j]
            k = k.astype(jnp.float32) * kscale_ref[hi * num_blocks + page]
            v = v.astype(jnp.float32) * vscale_ref[hi * num_blocks + page]
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / np.sqrt(q.shape[-1]))
        if softcap:                        # gemma2 attn_logit_softcapping
            s = softcap * jnp.tanh(s / softcap)
        # row r of the fold is (q-head r // chunk, chunk token r % chunk)
        row = i * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qpos = start + row % chunk
        kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= qpos                # causal == context-length mask
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = j * block_size <= max_qpos      # page overlaps the causal horizon
    if window is not None:
        live = jnp.logical_and(
            live, (j + 1) * block_size - 1 > min_qpos - window)
    pl.when(live)(_compute)

    @pl.when(j == num_pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, start_pos,
                    window=None, softcap=None, k_scales=None, v_scales=None,
                    interpret: bool = False):
    """q: [B, T, H, d] (T=1 decode / B=1 prefill chunk);
    k_pages/v_pages: [Hkv, NB, block_size, d]; block_tables: [B, MB] int32
    (trash-padded); start_pos: [B] int32 — global position of q row t=0
    (row t attends kpos <= start+t). ``k_scales``/``v_scales``: optional
    [Hkv, NB] fp32 per-(head, page) dequant scales for fp8 pages (ride as
    scalar prefetch; applied on load in-kernel). Returns [B, T, H, d].

    The KV written for q's own tokens must already be in the pages (the decode/
    prefill step scatters K/V before calling attention); causal masking then
    doubles as the context-length mask, so trash-padded table slots and stale
    tail entries of the last page are never visible.
    """
    return _paged_call(q, k_pages, v_pages, 0, 0, k_pages.shape[0],
                       block_tables, start_pos, window, softcap, k_scales,
                       v_scales, interpret)


def paged_attention_pool(q, pool, layer: int, block_tables, start_pos,
                         window=None, softcap=None, scales=None,
                         interpret: bool = False):
    """``paged_attention`` over layer ``layer`` (a Python int) of the whole
    KV pool [L, 2, Hkv, NB, block_size, d] (``scales``: [L, 2, Hkv, NB]).
    The pool goes to the kernel as it lies in memory, its leading dimensions
    merged, and the index maps start at the layer's K and V heads: a step
    program that handed ``pool[layer, 0]`` and ``pool[layer, 1]`` to the
    kernel copied each out first, the whole pool once a step."""
    hkv = pool.shape[2]
    pages = pool.reshape((-1,) + pool.shape[3:])
    ks, vs = (scales[layer, 0], scales[layer, 1]) if scales is not None \
        else (None, None)
    return _paged_call(q, pages, pages, 2 * layer * hkv,
                       (2 * layer + 1) * hkv, hkv, block_tables, start_pos,
                       window, softcap, ks, vs, interpret)


def _paged_call(q, k_pages, v_pages, k_head0: int, v_head0: int, hkv: int,
                block_tables, start_pos, window, softcap, k_scales, v_scales,
                interpret: bool):
    """The kernel call: KV head ``hi`` of the grid reads row ``k_head0 + hi``
    of ``k_pages`` and row ``v_head0 + hi`` of ``v_pages`` ([X, NB, bs, d])."""
    b, t, h, d = q.shape
    _, nb, bs, _ = k_pages.shape
    rep = h // hkv
    g = rep * t
    # fold rows per grid cell: a sublane multiple, capped for scoped VMEM
    rows = min(-(-g // 8) * 8, max(_MAX_FOLD_ELEMS // d // 16 * 16, 16))
    gp = -(-g // rows) * rows
    mb = block_tables.shape[1]
    scaled = k_scales is not None

    qf = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, d)
    if gp != g:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if scaled else 2,
        grid=(b, hkv, gp // rows, mb),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), lambda bi, hi, i, j, *pf:
                         (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda bi, hi, i, j, *pf, mb=mb:
                         (k_head0 + hi, pf[0][bi * mb + j], 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda bi, hi, i, j, *pf, mb=mb:
                         (v_head0 + hi, pf[0][bi * mb + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d), lambda bi, hi, i, j, *pf:
                               (bi, hi, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    prefetch = [block_tables.reshape(-1).astype(jnp.int32),
                start_pos.astype(jnp.int32)]
    if scaled:
        prefetch += [k_scales.reshape(-1).astype(jnp.float32),
                     v_scales.reshape(-1).astype(jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, num_pages=mb,
                          chunk=t, rows=rows, window=window, softcap=softcap,
                          num_blocks=nb if scaled else 0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(*prefetch, qf, k_pages, v_pages)

    out = out[:, :, :g].reshape(b, hkv, rep, t, d)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d)


def paged_attention_reference(q, k_pages, v_pages, block_tables, start_pos,
                              window=None, softcap=None, k_scales=None,
                              v_scales=None):
    """Gather-based jnp reference with identical semantics (numerics oracle for
    kernel tests; also the CPU fallback path). ``softcap`` tanh-caps the
    scaled logits before masking (gemma2 attn_logit_softcapping);
    ``k_scales``/``v_scales``: [Hkv, NB] per-(head, page) fp8 dequant."""
    b, t, h, d = q.shape
    hkv, _, bs, _ = k_pages.shape
    rep = h // hkv
    mb = block_tables.shape[1]
    # [Hkv, B, MB, bs, d] -> [B, MB*bs, Hkv, d]
    gk = k_pages[:, block_tables]
    gv = v_pages[:, block_tables]
    if k_scales is not None:               # dequant before the dtype fold
        gk = gk.astype(jnp.float32) * k_scales[:, block_tables][..., None, None]
        gv = gv.astype(jnp.float32) * v_scales[:, block_tables][..., None, None]
    ctx_k = gk.transpose(1, 2, 3, 0, 4).reshape(b, mb * bs, hkv, d)
    ctx_v = gv.transpose(1, 2, 3, 0, 4).reshape(b, mb * bs, hkv, d)
    if rep > 1:
        ctx_k = jnp.repeat(ctx_k, rep, axis=2)
        ctx_v = jnp.repeat(ctx_v, rep, axis=2)
    ctx_k = ctx_k.astype(q.dtype)          # fp8 pages dequantize on load
    ctx_v = ctx_v.astype(q.dtype)
    s = jnp.einsum("bthd,bkhd->bhtk", q, ctx_k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    from deepspeed_tpu.models.llama import softcap_logits
    s = softcap_logits(s, softcap)
    qpos = start_pos[:, None] + jnp.arange(t)[None, :]          # [B, T]
    kpos = jnp.arange(mb * bs)[None, None, :]
    mask = kpos <= qpos[..., None]
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos[..., None] - window)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(ctx_v.dtype)
    return jnp.einsum("bhtk,bkhd->bthd", p, ctx_v)
