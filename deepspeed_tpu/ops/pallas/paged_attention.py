"""Pallas paged (blocked) attention over the serving KV cache.

Reference analog: ``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``
(flash attention over paged KV) + ``atom_builder`` (ragged batch splitting).

TPU design: the block table rides as a **scalar-prefetch** argument
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index maps dereference it
and the kernel DMAs each sequence's KV pages *directly out of the paged pool in
HBM* — the gather fallback's [B, MB*bs, H, d] context re-materialization (plus
rep-times KV expansion for GQA) never exists.

The grid is ``(batch, KV-head block, row block, key tile)`` with the key tile
innermost: online-softmax accumulators live in VMEM scratch and carry across
tiles, flash-style. **A grid step reads ``P`` consecutive entries of the
sequence's block table**: ``P`` K slots and ``P`` V slots, each a BlockSpec of
one ``(block_size, d)`` page a KV head whose index map reads its own table
entry, so the pages still come straight out of the pool. In the kernel the
slots are joined into ONE ``[P * block_size, d]`` key tile and one value tile:
one ``q k^T``, one softmax update and one ``p v`` a step. A page a step (what
this kernel took until PR 36) costs the latency of those two small dependent
products through the softmax's scratch, not its bytes: a decode fold of 8 rows
over a 260-block table ran at a sixteenth of its memory roofline. The table is
padded to whole steps with block 0; a padded slot's positions lie above every
query's, so the causal mask hides it as it hides trash entries. Tiles
entirely above a row block's causal horizon (or entirely below its sliding
window) are predicated out with ``pl.when``; the mask itself is per position.

GQA/T folding: the q rows of one KV head are the (group × chunk) fold —
``rep`` query heads that share the KV head times ``T`` chunk tokens —
zero-padded to a multiple of 8 sublanes. Decode is T=1; prefill is B=1,
T=chunk. A fold too tall for the compiler's scoped VMEM (a 2048-token chunk of
a 4-way group is 8192 rows) is cut into row blocks on a grid axis of their own.

**A short fold takes every KV head of a key tile in one grid step** (PR 40).
The cache is head-major, so one table entry's page of ALL the layer's KV
heads is one strided block ``(Hkv, 1, block_size, d)``: one operand, one copy,
``Hkv`` times the bytes. The q and output blocks are ``(1, Hkv, rows, d)``, the
scratch ``[Hkv, rows, ...]``, the products batched over the heads, and the
grid's head axis has one step; a head's mathematics is what it was (the same
tile, the same order of the softmax's sums: the outputs agree bit for bit on
the chip). What it buys is the count of copies: with a head a step a decode
fold paid 47-56 ns for each 16 KB slot copy whatever the table held, its
index map, its read of the table in SMEM and its wait, 43% of the bandwidth
at best, and a dead slot cost what a live one cost.

**The tile follows the work** (``_tile``; static shapes alone choose, there is
no option). The key tile is as wide as the table up to ``_MAX_PAGES`` = 8
pages (512 keys), a power of two; the row block is the fold, or for a taller
fold the largest power of two of rows that the scoped VMEM holds beside that
key tile: 2,048 rows beside 8 pages (``_SCOPED_VMEM_BYTES`` says what is
counted). A fold that is one row block takes all ``Hkv`` heads a step where
that many folds and tiles fit the scoped VMEM by the same count (every decode
fold: 8 heads x 8-16 rows beside 8 pages are 6.7 MiB; up to 192 rows), else
one head, which is the kernel of PR 36 to the letter (every chunk of the
served cells: 2,048 rows a head). Swept on a v5e (PERF.md section 6, PR 36):
a decode fold of 8 rows over a 260-block table takes 11.8 ms a layer at one
page a step, 6.3 at 8, 6.1 at 16 and 6.4 at 32; a 4,096-token chunk of six
heads a KV head 73.5 ms at one page, 15.0 at 2,048 rows x 8 pages and 12.7 at
1,024 x 16: each step pays a pass over the accumulator whatever its keys, so
fewer, wider steps win. And (PR 40, trash-padded tables as the engine pads
them, 1 / 2 / 4 / 8 heads a step): that decode fold 6.40 / 3.38 / 2.01 / 1.45
ms (random tables: 6.39 at one head, 3.02 at eight, where the dead slots'
2.2 GB are fetched: a trash-padded dead slot repeats its block index and is
not copied again); 32 x 4 heads over 64 blocks 1.80 / 1.03 / 0.72 / 0.61;
16 x 4 over 32 blocks 0.52 / 0.33 / 0.25 / 0.22; 32 x 9 over 9 blocks behind
a window 0.65 / 0.39 / 0.28 / 0.26. What holds the tile at 8 pages is the
host: a slot is an operand of the call, and every operand costs a step
program's first call (the served cells' `setup_s`); heads a step add none.
A row block cut down to a sliding window's 512 rows loses to 1,024 and 2,048
(6.6 against 5.9 and 6.1 ms for Laguna's sliding chunk at 8 pages): a narrower
block multiplies fewer masked pairs, but every row block walks the whole table.

The call sits under a ``jit`` of its own and what differs between the layers
of a pool (where the layer's K and V heads start) is a prefetched scalar, not a
constant of the trace: a step program traces and lowers ONE kernel a layer kind
and calls it once a layer.

Cache layout is head-major ``[Hkv, num_blocks, block_size, d]`` so one page of
one KV head is a contiguous ``(block_size, d)`` tile (legal TPU block shape),
and one table entry's page of every head ``Hkv`` such tiles a stride apart.

**Keys and values of their own widths, and a sink.** The K pages' rows are as
wide as q (``q k^T`` over ``dk``) and the V pages' as wide as the output
(``p v`` over ``dv``): two static shapes, one where a model's heads are square
(every tile and buffer count below takes both). ``scale`` is the softmax
scale where ``dk`` is not what it follows from: a key stored in rows padded
with zero lanes scores at its own width. ``sinks`` is a learned logit a query
head that joins the softmax's denominator and has no value: the online
softmax starts its running maximum at the row's sink and its running sum at
1 (``exp(sink - sink)``) where it otherwise starts them at -inf and 0, which
is exact and costs no pass. With neither, the kernel is traced and lowered
as it was.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# The kernel sets no compiler parameters, so it lives inside the default 16 MiB
# of scoped VMEM. What a grid step holds there, by the sizes the v5e's compiler
# refused and took (libtpu 0.0.34; PERF.md section 6, PR 36): a row of the
# fold keeps q (at the keys' width) and the output (at the values') twice
# (the pipeline's two buffers), the float32 accumulator, and the running
# maximum and sum at a lane tile each; a page of
# the key tile its K and V slots twice and once more joined; and every row of
# every page a float32 score and a float32 probability. 2,048 rows x 16 pages
# come to 22.5 MiB by that count and were refused at 22.2; 2,048 x 8 (13.8) and
# 1,024 x 16 (12.0) compile. A step of several KV heads holds all of it a
# head: 8 heads x 192 rows x 8 pages (15.9) compile (PR 40).
_SCOPED_VMEM_BYTES = 16 * 2 ** 20
# Tallest q fold one grid cell takes whatever the key tile, in rows x head_dim
# elements: 4096 rows x d128 over one page still compile and 8192 rows are
# refused. Half the largest size that fits leaves the margin.
_MAX_FOLD_ELEMS = 2048 * 128
# Most pages a step joins. From 8 to 16 a decode fold of 8 rows gains 3-17%
# and a chunk 15-25% (and from 16 to 32 nothing), but every slot is an operand
# of the call, and a step program's first call costs the host 12 ms a slot a
# program before its first result (warm `setup_s`: +8.6% at 16 in the
# Mixtral cell, +4.8% at 8; PERF.md section 6, PR 36), and a context rounds up
# to whole tiles.
_MAX_PAGES = 8


def _tile_pages(mb: int) -> int:
    """Pages of a key tile over a table of ``mb`` blocks: a power of two, so
    that a tile's keys are whole lane tiles; a table shorter than
    ``_MAX_PAGES`` is one step."""
    return 1 << (min(mb, _MAX_PAGES) - 1).bit_length()


def _tile(g: int, mb: int, bs: int, d: int, itemsize: int, hkv: int = 1,
          dv: int = 0):
    """``(rows, pages, heads)`` of one grid step for a fold of ``g`` rows a
    KV head, ``hkv`` of them, over a table of ``mb`` blocks, keys ``d`` wide
    and values ``dv`` (left out: ``d``): the key tile as wide as the table
    and ``_MAX_PAGES`` allow, then the row block as tall as the scoped VMEM
    holds beside it, a power of two so that it divides the chunk buckets;
    then every KV head in the step where the fold is one row block and
    ``hkv`` such blocks and their tiles fit the scoped VMEM by the same
    count, else one. The count (``_SCOPED_VMEM_BYTES``): a row keeps q
    (``d``) and the output (``dv``) twice and the accumulator (``dv``) in
    float32; a page its K slot (``d``) and its V slot (``dv``) twice and
    once more joined."""
    dv = dv or d
    pages = _tile_pages(mb)
    a_row = 2 * (d + dv) * itemsize + 4 * dv + 2 * 128 * 4 + 8 * pages * bs
    a_tile = 3 * pages * bs * (d + dv) * itemsize
    room = (_SCOPED_VMEM_BYTES - a_tile) // a_row
    rows = min(1 << (room.bit_length() - 1),
               max(_MAX_FOLD_ELEMS // d // 16 * 16, 16))
    fold = -(-g // 8) * 8                  # a sublane multiple
    if fold > rows:
        return rows, pages, 1
    fits = hkv * (fold * a_row + a_tile) <= _SCOPED_VMEM_BYTES
    return fold, pages, hkv if fits else 1


def decode_tile_keys(contexts, mb: int, bs: int, window=None) -> int:
    """Keys the kernel's live steps cover for a decode batch over tables of
    ``mb`` blocks: each context rounded out to whole key tiles (from the tile
    that holds the start of its window, where there is one). ``contexts``
    over it is the tiles' fill."""
    tile = _tile_pages(mb) * bs
    return sum((-(-c // tile) - (max(c - window, 0) // tile if window else 0))
               * tile for c in contexts)


def decode_slot_copies(batch: int, hkv: int, mb: int, bs: int, d: int,
                       itemsize: int, group: int = 1, dv: int = 0) -> int:
    """Slot copies one layer's decode call issues for ``batch`` rows of
    ``group`` query heads a KV head over tables of ``mb`` blocks: a K and a V
    slot a page of every step of the grid ``(batch, hkv // heads, 1,
    steps)``, dead steps too (a copy costs what it costs whether its page is
    live: what a decode call's seconds divide by). ``group`` decides only
    whether ``hkv`` folds still fit a step: left out, a fold of one sublane
    tile."""
    _, pages, heads = _tile(group, mb, bs, d, itemsize, hkv, dv)
    return batch * (hkv // heads) * -(-mb // pages) * pages * 2


def _paged_kernel(*refs, block_size, pages, steps, chunk, rows, heads,
                  window, softcap, scale, sunk=False, num_blocks=0):
    tables_ref, start_ref, _ = refs[:3]
    refs = refs[3:]
    kscale_ref = vscale_ref = sink_ref = None
    if num_blocks:      # fp8 pages with per-(head, page) scales prefetched
        kscale_ref, vscale_ref = refs[:2]
        refs = refs[2:]
    q_ref = refs[0]
    k_refs, v_refs = refs[1:1 + pages], refs[1 + pages:1 + 2 * pages]
    refs = refs[1 + 2 * pages:]
    if sunk:            # a sink logit a row of the fold, [(heads,) rows, 1]
        sink_ref, refs = refs[0], refs[1:]
    o_ref, m_scr, l_scr, acc_scr = refs
    tile = pages * block_size
    b = pl.program_id(0)
    hi = pl.program_id(1)                  # block of ``heads`` KV heads
    i = pl.program_id(2)                   # row block of the q fold
    j = pl.program_id(3)                   # key tile
    # one KV head a step works on [rows, d] and [keys, d] as it always has;
    # several carry them as the leading axis of every array and product
    many = heads > 1
    head = slice(None) if many else 0      # of a q, slot or output block
    batch = ((0,), (0,)) if many else ((), ())

    @pl.when(j == 0)
    def _init():
        if sunk:
            # the sink is in the denominator before any key: exp(b - b) = 1
            m_scr[:] = sink_ref[head]
            l_scr[:] = jnp.ones_like(l_scr)
        else:
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

    def _joined(slot_refs, scale_ref, dtype):
        # [bs, d] each, or [heads, bs, d]: a table entry's page of every head
        slots = [r[head, 0] for r in slot_refs]
        if scale_ref is not None:
            # fp8 pages dequantize on load, slot by slot and head by head:
            # each page's scale rides in SMEM next to the block table
            entry = (b * steps + j) * pages

            def scaled(s, h, p):
                return s.astype(jnp.float32) * scale_ref[
                    (hi * heads + h) * num_blocks + tables_ref[entry + p]]
            slots = [jnp.stack([scaled(s[h], h, p) for h in range(heads)])
                     if many else scaled(s, 0, p)
                     for p, s in enumerate(slots)]
        return jnp.concatenate([s.astype(dtype) for s in slots], axis=-2)

    def _compute():
        q = q_ref[0, head]                           # [(heads,) rows, d]
        k = _joined(k_refs, kscale_ref, q.dtype)     # [(heads,) P * bs, d]
        v = _joined(v_refs, vscale_ref, q.dtype)
        s = jax.lax.dot_general(q, k, (((q.ndim - 1,), (k.ndim - 1,)), batch),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap:                        # gemma2 attn_logit_softcapping
            s = softcap * jnp.tanh(s / softcap)
        # row r of the fold is (q-head r // chunk, chunk token r % chunk)
        row = i * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                  s.ndim - 2)
        qpos = start + row % chunk
        kpos = j * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   s.ndim - 1)
        mask = kpos <= qpos                # causal == context-length mask
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((p.ndim - 1,), (v.ndim - 2,)), batch),
            preferred_element_type=jnp.float32)

    live = j * tile <= max_qpos            # tile overlaps the causal horizon
    if window is not None:
        live = jnp.logical_and(live, (j + 1) * tile - 1 > min_qpos - window)
    pl.when(live)(_compute)

    @pl.when(j == steps - 1)
    def _finalize():
        o_ref[0, head] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                          ).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, start_pos,
                    window=None, softcap=None, k_scales=None, v_scales=None,
                    sinks=None, scale=None, interpret: bool = False):
    """q: [B, T, H, dk] (T=1 decode / B=1 prefill chunk);
    k_pages: [Hkv, NB, block_size, dk]; v_pages: [Hkv, NB, block_size, dv];
    block_tables: [B, MB] int32 (trash-padded); start_pos: [B] int32 — global
    position of q row t=0 (row t attends kpos <= start+t).
    ``k_scales``/``v_scales``: optional [Hkv, NB] fp32 per-(head, page)
    dequant scales for fp8 pages (ride as scalar prefetch; applied on load
    in-kernel). ``sinks``: optional [H] logits, one a query head, that join
    the softmax's denominator. ``scale``: the softmax scale, ``dk ** -0.5``
    left out. Returns [B, T, H, dv].

    The KV written for q's own tokens must already be in the pages (the decode/
    prefill step scatters K/V before calling attention); causal masking then
    doubles as the context-length mask, so trash-padded table slots and stale
    tail entries of the last page are never visible.
    """
    return _paged_call(q, k_pages, v_pages, jnp.zeros((2,), jnp.int32),
                       block_tables, start_pos, k_scales, v_scales, sinks,
                       hkv=k_pages.shape[0], window=window, softcap=softcap,
                       scale=_softmax_scale(q, scale), interpret=interpret)


def _softmax_scale(q, scale) -> float:
    return float(1.0 / np.sqrt(q.shape[-1])) if scale is None \
        else float(scale)


def paged_attention_pool(q, pool, layer, block_tables, start_pos,
                         window=None, softcap=None, scales=None, sinks=None,
                         scale=None, interpret: bool = False):
    """``paged_attention`` over layer ``layer`` of the whole KV pool: K and V
    planes of one array [L, 2, Hkv, NB, block_size, d] (``scales``:
    [L, 2, Hkv, NB]), or where keys and values have widths of their own a
    pair ``(K [L, Hkv, NB, block_size, dk], V [L, Hkv, NB, block_size,
    dv])``. A pool goes to the kernel as it lies in memory, its leading
    dimensions merged, and the index maps start at the layer's K and V
    heads: a step program that handed ``pool[layer, 0]`` and
    ``pool[layer, 1]`` to the kernel copied each out first, the whole pool
    once a step. Where those heads start is a value handed to the kernel, so
    the layers of a pool share one traced and lowered call."""
    if isinstance(pool, tuple):
        k_pool, v_pool = pool
        hkv = k_pool.shape[1]
        k_pages = k_pool.reshape((-1,) + k_pool.shape[2:])
        v_pages = v_pool.reshape((-1,) + v_pool.shape[2:])
        heads0 = jnp.asarray([layer * hkv, layer * hkv], jnp.int32)
    else:
        hkv = pool.shape[2]
        k_pages = v_pages = pool.reshape((-1,) + pool.shape[3:])
        heads0 = jnp.asarray([2 * layer * hkv, (2 * layer + 1) * hkv],
                             jnp.int32)
    ks, vs = (scales[layer, 0], scales[layer, 1]) if scales is not None \
        else (None, None)
    return _paged_call(q, k_pages, v_pages, heads0, block_tables, start_pos,
                       ks, vs, sinks, hkv=hkv, window=window, softcap=softcap,
                       scale=_softmax_scale(q, scale), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("hkv", "window", "softcap",
                                             "scale", "interpret"))
def _paged_call(q, k_pages, v_pages, heads0, block_tables, start_pos,
                k_scales, v_scales, sinks=None, *, hkv: int, window, softcap,
                scale: float, interpret: bool):
    """The kernel call: step ``hi`` of the grid's head axis reads the
    ``heads`` rows of ``k_pages`` ([X, NB, bs, dk]) from ``heads0[0] + hi *
    heads`` and those of ``v_pages`` ([Y, NB, bs, dv]) from ``heads0[1] + hi
    * heads`` (``heads`` is ``_tile``'s: ``hkv`` or 1). A function of its own
    under ``jit`` so that a step program traces and lowers it once a layer
    kind and not once a layer."""
    b, t, h, d = q.shape
    _, nb, bs, _ = k_pages.shape
    dv = v_pages.shape[-1]
    rep = h // hkv
    g = rep * t
    mb = block_tables.shape[1]
    rows, pages, heads = _tile(g, mb, bs, d, k_pages.dtype.itemsize, hkv, dv)
    gp = -(-g // rows) * rows
    steps = -(-mb // pages)
    scaled = k_scales is not None
    sunk = sinks is not None

    qf = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, d)
    if gp != g:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    # whole steps: a padded entry's positions lie above every query's
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, steps * pages - mb)))

    def slot(kv, p, width):
        # slot p of a step reads its own entry of the sequence's table: that
        # block of ``heads`` KV heads, one strided copy for all of them
        return pl.BlockSpec(
            (heads, 1, bs, width), lambda bi, hi, i, j, *pf:
            (pf[2][kv] + hi, pf[0][(bi * steps + j) * pages + p], 0, 0))

    def rows_spec(width):
        return pl.BlockSpec((1, heads, rows, width),
                            lambda bi, hi, i, j, *pf: (bi, hi, i, 0))

    lead = (heads,) if heads > 1 else ()
    extra, extra_specs = [], []
    if sunk:
        # row r of a KV head's fold is query head r // t of its group: the
        # sink of every row, [Hkv, gp, 1] (0 on the fold's padding rows)
        per_row = jnp.repeat(sinks.astype(jnp.float32).reshape(hkv, rep), t,
                             axis=1)
        extra = [jnp.pad(per_row, ((0, 0), (0, gp - g)))[..., None]]
        extra_specs = [pl.BlockSpec((heads, rows, 1),
                                    lambda bi, hi, i, j, *pf: (hi, i, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if scaled else 3,
        grid=(b, hkv // heads, gp // rows, steps),
        in_specs=[rows_spec(d)] + [slot(0, p, d) for p in range(pages)]
        + [slot(1, p, dv) for p in range(pages)] + extra_specs,
        out_specs=rows_spec(dv),
        scratch_shapes=[
            pltpu.VMEM(lead + (rows, 1), jnp.float32),
            pltpu.VMEM(lead + (rows, 1), jnp.float32),
            pltpu.VMEM(lead + (rows, dv), jnp.float32),
        ],
    )
    heads0 = heads0.astype(jnp.int32)
    if heads > 1:
        # in blocks of ``heads``: a layer's K and V heads start at a multiple
        # of ``hkv``
        heads0 = jax.lax.div(heads0, jnp.int32(heads))
    prefetch = [tables.reshape(-1), start_pos.astype(jnp.int32), heads0]
    if scaled:
        prefetch += [k_scales.reshape(-1).astype(jnp.float32),
                     v_scales.reshape(-1).astype(jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, pages=pages,
                          steps=steps, chunk=t, rows=rows, heads=heads,
                          window=window, softcap=softcap, scale=scale,
                          sunk=sunk, num_blocks=nb if scaled else 0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, dv), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(*prefetch, qf, *[k_pages] * pages, *[v_pages] * pages, *extra)

    out = out[:, :, :g].reshape(b, hkv, rep, t, dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, dv)


def paged_attention_reference(q, k_pages, v_pages, block_tables, start_pos,
                              window=None, softcap=None, k_scales=None,
                              v_scales=None, sinks=None, scale=None):
    """Gather-based jnp reference with identical semantics (numerics oracle for
    kernel tests; also the CPU fallback path). ``softcap`` tanh-caps the
    scaled logits before masking (gemma2 attn_logit_softcapping);
    ``k_scales``/``v_scales``: [Hkv, NB] per-(head, page) fp8 dequant;
    ``sinks``: [H] logits that join each head's denominator as one more
    column with no value; ``scale``: the softmax scale, ``dk ** -0.5`` left
    out. v_pages: [Hkv, NB, bs, dv]; returns [B, T, H, dv]."""
    b, t, h, d = q.shape
    hkv, _, bs, _ = k_pages.shape
    dv = v_pages.shape[-1]
    rep = h // hkv
    mb = block_tables.shape[1]
    # [Hkv, B, MB, bs, d] -> [B, MB*bs, Hkv, d]
    gk = k_pages[:, block_tables]
    gv = v_pages[:, block_tables]
    if k_scales is not None:               # dequant before the dtype fold
        gk = gk.astype(jnp.float32) * k_scales[:, block_tables][..., None, None]
        gv = gv.astype(jnp.float32) * v_scales[:, block_tables][..., None, None]
    ctx_k = gk.transpose(1, 2, 3, 0, 4).reshape(b, mb * bs, hkv, d)
    ctx_v = gv.transpose(1, 2, 3, 0, 4).reshape(b, mb * bs, hkv, dv)
    if rep > 1:
        ctx_k = jnp.repeat(ctx_k, rep, axis=2)
        ctx_v = jnp.repeat(ctx_v, rep, axis=2)
    ctx_k = ctx_k.astype(q.dtype)          # fp8 pages dequantize on load
    ctx_v = ctx_v.astype(q.dtype)
    s = jnp.einsum("bthd,bkhd->bhtk", q, ctx_k,
                   preferred_element_type=jnp.float32)
    s = s / np.sqrt(d) if scale is None else s * scale
    from deepspeed_tpu.models.llama import softcap_logits
    s = softcap_logits(s, softcap)
    qpos = start_pos[:, None] + jnp.arange(t)[None, :]          # [B, T]
    kpos = jnp.arange(mb * bs)[None, None, :]
    mask = kpos <= qpos[..., None]
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos[..., None] - window)
    s = jnp.where(mask[:, None], s, NEG_INF)
    if sinks is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32)[None, :, None, None], s.shape[:3] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, sink], -1), axis=-1)[..., :-1]
    return jnp.einsum("bhtk,bkhd->bthd", p.astype(ctx_v.dtype), ctx_v)
