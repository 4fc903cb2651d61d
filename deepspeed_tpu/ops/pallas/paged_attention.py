"""Pallas paged (blocked) attention over the serving KV cache.

Reference analog: ``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``
(flash attention over paged KV) + ``atom_builder`` (ragged batch splitting).

TPU design: **the K pool and the V pool go to the call once each, whole, as
they lie in HBM** (``memory_space=ANY``: no block, no pipeline, no copy of
the pool), and the block table rides as a **scalar-prefetch** argument. The
kernel reads the table itself and starts one DMA a table entry
(``pltpu.make_async_copy`` out of ``pool.at[head, table[entry]]``) straight
into the entry's place in a joined ``[P * block_size, d]`` key tile and value
tile in VMEM, two buffers deep: the next tile's copies are started before
this tile's products and waited for after them, across grid steps too (the
last tile of a step starts the first tile of the step after it, which is why
the grid's axes are all ``arbitrary``: in order, on one core). The gather
fallback's [B, MB*bs, H, d] context re-materialization (plus rep-times KV
expansion for GQA) never exists.

The grid is ``(batch, KV-head block, row block)``. A grid step holds a row
block of the q fold and walks the key tiles its rows can see in a loop of its
own, the online softmax's running maximum, sum and accumulator in VMEM
scratch: one ``q k^T``, one softmax update and one ``p v`` a tile of ``P``
pages. **Only what a row block sees is fetched or multiplied** (``_live_
entries``): the table entries from the page that holds the first key the
block's first query sees through its window (entry 0 where there is none)
up to the page of its last query's own position, or the table's end. The
tiles start at that first entry, not at a multiple of ``P``; a last tile
copies only its live entries (the rest of the buffer keeps finite rows of an
earlier tile, masked by position). So a tile above a row block's causal
horizon, below its window, or past the context is neither copied nor waited
for, a padding row of a decode batch costs one page, and behind a window the
key loop is as long as the window's span, not the table. Until PR 42 every
page of a tile was an operand of the call (a pipelined ``BlockSpec`` a page,
``2 P`` of them, joined in the kernel with a ``concatenate``), which held the
tile at 8 pages for the sake of the host (an operand cost a step program's
first call 6 ms) and fetched every tile of a live table whatever it held.

GQA/T folding: the q rows of one KV head are the (group × chunk) fold —
``rep`` query heads that share the KV head times ``T`` chunk tokens —
zero-padded to a multiple of 8 sublanes. Decode is T=1; prefill is B=1,
T=chunk. A tall fold is cut into row blocks on a grid axis of their own.

**A short fold takes every KV head of a key tile in one grid step** (PR 40).
The cache is head-major, so one table entry's page of ALL the layer's KV
heads is one strided copy ``(Hkv, block_size, d)``, ``Hkv`` times the bytes.
The q and output blocks are ``(1, Hkv, rows, d)``, the scratch ``[Hkv, rows,
...]``, the products batched over the heads, and the grid's head axis has one
step; a head's mathematics is what it is with a head a step. What it buys is
the count of copies: a decode fold is bound by them, not by their bytes.

**The tile follows the work** (``_tile``; static shapes and ``window`` alone
choose, there is no option), and the call asks the compiler for the VMEM its
own tile counts to (``_vmem_bytes``, ``_vmem_limit``; the default 16 MiB
refused 2,048 rows beside 16 pages at 22 MiB on a chip with 128). Swept on a
v5e, a call of one layer, ms:

- PR 36 (slots): a decode fold of 8 rows over a 260-block table 11.8 at one
  page a step, 6.3 at 8, 6.1 at 16, 6.4 at 32; a 4,096-token chunk of 48
  heads ending at 12,288 73.5 at one page, 15.0 at 2,048 rows x 8 pages, 12.7
  at 1,024 x 16: each step pays a pass over the ``[rows, dv]`` float32
  accumulator whatever its keys, so fewer, wider steps win.
- PR 40 (slots; 1 / 2 / 4 / 8 heads a step, trash-padded tables): that decode
  fold 6.40 / 3.38 / 2.01 / 1.45; 32 x 4 heads over 64 blocks 1.80 / 1.03 /
  0.72 / 0.61; 32 x 9 over 9 blocks behind a window 0.65 / 0.39 / 0.28 / 0.26.
- PR 42 (the kernel's own copies; the parent's slots first). Full chunks of
  4,096 tokens, (rows, pages): MiMo's 64 heads over 4 KV heads, keys of 256
  lanes beside values of 128, ending at 24,960: parent (1,024, 8) 60.8;
  (1,024, 8) 56.9, (2,048, 8) 54.6, (512, 16) 41.9, (1,024, 16) 37.5,
  **(2,048, 16) 35.4**, (2,048, 32) 36.8; ending at 12,288: parent 33.0,
  (2,048, 16) 16.7, (2,048, 32) 16.8. Laguna's 48 heads of 128 ending at
  12,288: parent (2,048, 8) 15.1; (2,048, 8) 13.8, (1,024, 16) 10.2,
  **(2,048, 16) 9.8**, (2,048, 32) 9.9. Mixtral's 2,048 tokens ending at
  3,072: parent 1.51, (2,048, 16) 1.05. Windowed chunks of 4,096 tokens:
  MiMo's window of 128 with sinks, 8 KV heads: parent (1,024, 8) 6.42;
  (2,048, 16) 6.04, (1,024, 8) 4.93, (512, 8) 3.66, (256, 4) 3.83, (512, 16)
  3.20, (128, 8) 3.70, (256, 16) 3.46, **(256, 8) 2.92**. Laguna's window of
  512, 72 heads: parent (2,048, 8) 6.97; (2,048, 16) 5.26, (1,024, 16) 3.97,
  (1,024, 32) 3.86, (256, 8) 3.82, (512, 16) 4.16, **(256, 16) 3.19**. What
  wins behind a window is ONE tile a row block: 256 rows see ``256 + window
  - 1`` keys from wherever in a page the first falls, and the smallest tile
  that holds them (8 pages at 128, 16 at 512) makes a grid step one product
  with no rescaling pass; a taller block multiplies more masked pairs a row.
  Decode, 32 rows, parent's slots / own copies: chat's 8 heads over 32 blocks
  0.301 / 0.226; Mixtral's over 64 0.545 / 0.409; Laguna's over 260 1.98 /
  1.57 and 9 blocks behind its window 0.211 / 0.198; MiMo's 4 heads over 390
  2.63 / 2.25 and 8 heads over 3 blocks with sinks 0.210 / 0.213 (a step of
  one short tile is the scalar work of starting and awaiting its copies,
  whoever starts them). 16 pages a decode step read as 8 do (0.227, 0.408,
  1.574, 2.246): ``_MAX_PAGES`` stays 8 there.

The call sits under a ``jit`` of its own and what differs between the layers
of a pool (where the layer's K and V heads start) is a prefetched scalar, not a
constant of the trace: a step program traces and lowers ONE kernel a layer kind
and calls it once a layer.

Cache layout is head-major ``[Hkv, num_blocks, block_size, d]`` so one page of
one KV head is a contiguous ``(block_size, d)`` tile, and one table entry's
page of every head ``Hkv`` such tiles a stride apart.

**Keys and values of their own widths, and a sink.** The K pages' rows are as
wide as q (``q k^T`` over ``dk``) and the V pages' as wide as the output
(``p v`` over ``dv``): two static shapes, one where a model's heads are square
(every tile and buffer count below takes both). ``scale`` is the softmax
scale where ``dk`` is not what it follows from: a key stored in rows padded
with zero lanes scores at its own width. ``sinks`` is a learned logit a query
head that joins the softmax's denominator and has no value: the online
softmax starts its running maximum at the row's sink and its running sum at
1 (``exp(sink - sink)``) where it otherwise starts them at -inf and 0, which
is exact and costs no pass.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# What a grid step may hold in VMEM by ``_vmem_bytes``' count; the call asks
# the compiler for what its own tile counts to (``_vmem_limit``), not for
# this. A v5e has 128 MiB.
_SCOPED_VMEM_BYTES = 64 * 2 ** 20
# The compiler's default: a call asks for no less, and a step of several KV
# heads is held to it (PR 40's folds: up to 192 rows of 8 heads).
_DEFAULT_VMEM_BYTES = 16 * 2 ** 20
# Tallest row block: a power of two, so that it divides the chunk buckets.
_MAX_ROWS = 2048
# Most pages of a key tile: where a step takes every KV head (every decode
# fold: 16 pages read the same on the chip), and where it takes one (every
# chunk: each step pays a pass over the ``[rows, dv]`` float32 accumulator,
# so 16 pages beat 8 by a third and 32 read as 16 do).
_MAX_PAGES = 8
_MAX_PAGES_CUT = 16
# Row block behind a window narrow enough that ONE tile of ``_MAX_PAGES_CUT``
# pages at most holds every key the block's rows see.
_WINDOW_ROWS = 256


def _pow2_ceil(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


def _tile_pages(mb: int) -> int:
    """Pages of a key tile of a fold that is one row block over a table of
    ``mb`` blocks: a power of two, so that a tile's keys are whole lane
    tiles; a table shorter than ``_MAX_PAGES`` is one step."""
    return _pow2_ceil(min(mb, _MAX_PAGES))


def _vmem_bytes(rows: int, pages: int, heads: int, bs: int, d: int, dv: int,
                itemsize: int) -> int:
    """VMEM one grid step holds, as the v5e's compiler has refused and taken
    it (libtpu 0.0.34): a row of the fold keeps q (at the keys' width) and
    the output (at the values') twice (the pipeline's two buffers), the
    float32 accumulator, and the running maximum and sum at a lane tile
    each; a page of the key tile its K and V rows in each of the two
    buffers the kernel copies into; and every row of every page a float32
    score and a float32 probability. A step of several KV heads holds all of
    it a head."""
    a_row = 2 * (d + dv) * itemsize + 4 * dv + 2 * 128 * 4 + 8 * pages * bs
    a_tile = 2 * pages * bs * (d + dv) * itemsize
    return heads * (rows * a_row + a_tile)


def _vmem_limit(rows, pages, heads, bs, d, dv, itemsize) -> int:
    """What the call asks the compiler for: its tile's count and a quarter
    more for what the compiler keeps beside it, the default at least."""
    count = _vmem_bytes(rows, pages, heads, bs, d, dv, itemsize)
    return max(count + count // 4, _DEFAULT_VMEM_BYTES)


def _tile(g: int, mb: int, bs: int, d: int, itemsize: int, hkv: int = 1,
          dv: int = 0, window=None):
    """``(rows, pages, heads)`` of one grid step for a fold of ``g`` rows a
    KV head, ``hkv`` of them, over a table of ``mb`` blocks, keys ``d`` wide
    and values ``dv`` (left out: ``d``), behind ``window`` where there is
    one. Static shapes alone choose:

    - a short fold (every decode fold) is one row block and takes every KV
      head in a step, over a tile as wide as the table up to ``_MAX_PAGES``,
      where ``hkv`` such folds and tiles count to the default VMEM;
    - any other takes one head a step and rows up to ``_MAX_ROWS``. Behind a
      window it is cut into blocks of ``_WINDOW_ROWS`` over the smallest tile
      that holds all a block's rows see (``rows + window - 1`` keys, from
      wherever in a page the first falls), one step a block, where
      ``_MAX_PAGES_CUT`` pages hold that; else its tile is as wide as the
      table up to ``_MAX_PAGES_CUT`` and its row block as tall as the budget
      holds beside it."""
    dv = dv or d
    fold = -(-g // 8) * 8                  # a sublane multiple
    pages = _tile_pages(mb)
    if _vmem_bytes(fold, pages, hkv, bs, d, dv, itemsize) \
            <= _DEFAULT_VMEM_BYTES:
        return fold, pages, hkv
    if window is not None:
        seen = -(-(_WINDOW_ROWS + window + bs - 2) // bs)
        if seen <= _MAX_PAGES_CUT:
            return min(fold, _WINDOW_ROWS), \
                min(_pow2_ceil(seen), _pow2_ceil(mb)), 1
    pages = _pow2_ceil(min(mb, _MAX_PAGES_CUT))
    rows = _MAX_ROWS
    while _vmem_bytes(rows, pages, 1, bs, d, dv, itemsize) \
            > _SCOPED_VMEM_BYTES:
        rows //= 2
    return min(fold, rows), pages, 1


def _rows_keys(lo_q: int, hi_q: int, mb: int, bs: int, pages: int, window):
    """``(keys, entries)`` of the tiles and of the table entries the kernel
    reads for one row block of queries at ``lo_q .. hi_q``."""
    first, end = _live_entries(lo_q, hi_q, mb, bs, window)
    entries = int(end - first)
    return -(-entries // pages) * pages * bs, entries


def decode_tile_keys(contexts, mb: int, bs: int, window=None) -> int:
    """Keys the kernel's tiles cover for a decode batch over tables of
    ``mb`` blocks: each context's live table entries (from the block that
    holds the start of its window, where there is one) rounded out to whole
    key tiles. ``contexts`` over it is the tiles' fill."""
    pages = _tile_pages(mb)
    return sum(_rows_keys(c - 1, c - 1, mb, bs, pages, window)[0]
               for c in contexts)


def decode_slot_copies(contexts, bucket: int, hkv: int, mb: int, bs: int,
                       d: int, itemsize: int, group: int = 1, dv: int = 0,
                       window=None) -> int:
    """Page copies one layer's decode call issues for a batch of ``contexts``
    padded to ``bucket`` rows of ``group`` query heads a KV head over tables
    of ``mb`` blocks: a K and a V copy a live table entry a step of the
    grid's head axis (a padding row reads the one key of position 0). What a
    decode call's seconds divide by. ``group`` decides only whether ``hkv``
    folds still fit a step: left out, a fold of one sublane tile."""
    _, pages, heads = _tile(group, mb, bs, d, itemsize, hkv, dv, window)
    last = [c - 1 for c in contexts] + [0] * (bucket - len(contexts))
    entries = sum(_rows_keys(p, p, mb, bs, pages, window)[1] for p in last)
    return entries * (hkv // heads) * 2


def chunk_tile_keys(start: int, bucket: int, group: int, hkv: int, mb: int,
                    bs: int, d: int, itemsize: int, dv: int = 0,
                    window=None):
    """``(tile_keys, tile_copies)`` of one layer's call for a chunk padded
    to ``bucket`` rows at positions ``start ..``, ``group`` query heads a KV
    head: the keys of the tiles the kernel multiplies a query row by, summed
    over the chunk's rows (one query head's: the visible pairs over it is
    the tiles' fill), and the K and V page copies the call issues for all
    its heads."""
    rows, pages, heads = _tile(group * bucket, mb, bs, d, itemsize, hkv, dv,
                               window)
    if bucket % rows == 0:                 # row blocks inside one q head
        spans = [(start + i, start + i + rows - 1)
                 for i in range(0, bucket, rows)]
        each, blocks = rows, group
    else:                                  # every block spans the chunk
        spans = [(start, start + bucket - 1)]
        each, blocks = bucket, -(-group * bucket // rows)
    read = [_rows_keys(lo, hi, mb, bs, pages, window) for lo, hi in spans]
    return sum(keys for keys, _ in read) * each, \
        sum(entries for _, entries in read) * blocks * (hkv // heads) * 2


def _live_entries(lo_q, hi_q, mb: int, bs: int, window, xp=np):
    """``(first, end)``: the table entries ``first <= e < end`` that hold a
    key some query at positions ``lo_q .. hi_q`` sees: up to the last
    query's own block (or the table's end: what lies past the context is
    padding), and behind a ``window`` from the block of the first query's
    oldest visible key. At least one (rows wholly past the table read its
    last entry, masked: a step with nothing to read would be a case of its
    own). ints or arrays with ``np``, traced scalars with ``jnp``."""
    end = xp.minimum(hi_q // bs + 1, mb)
    if window is None:
        return 0 * end, end
    first = xp.minimum(xp.maximum(lo_q - window + 1, 0) // bs, mb - 1)
    return first, xp.maximum(end, first + 1)


def _paged_kernel(*refs, block_size, pages, table_blocks, chunk, rows, heads,
                  window, softcap, scale, sunk=False, num_blocks=0):
    tables_ref, start_ref, heads0_ref = refs[:3]
    refs = refs[3:]
    kscale_ref = vscale_ref = sink_ref = None
    if num_blocks:      # fp8 pages with per-(head, page) scales prefetched
        kscale_ref, vscale_ref = refs[:2]
        refs = refs[2:]
    q_ref, k_pool, v_pool = refs[:3]
    refs = refs[3:]
    if sunk:            # a sink logit a row of the fold, [(heads,) rows, 1]
        sink_ref, refs = refs[0], refs[1:]
    o_ref, m_scr, l_scr, acc_scr, k_buf, v_buf, sems, slot_scr = refs
    bs, mb = block_size, table_blocks
    b = pl.program_id(0)
    hi = pl.program_id(1)                  # block of ``heads`` KV heads
    i = pl.program_id(2)                   # row block of the q fold
    # one KV head a step works on [rows, d] and [keys, d] as it always has;
    # several carry them as the leading axis of every array and product
    many = heads > 1
    head = slice(None) if many else 0      # of a q or output block
    batch = ((0,), (0,)) if many else ((), ())

    def span(b, i):
        """The live table entries of row block ``i`` of sequence ``b``."""
        lo_q = start_ref[b]
        if chunk % rows == 0:
            # the row block is a run of consecutive tokens of one q head
            lo_q = lo_q + (i * rows) % chunk
            hi_q = lo_q + rows - 1
        else:                              # it spans heads: the whole chunk
            hi_q = lo_q + chunk - 1
        return _live_entries(lo_q, hi_q, mb, bs, window, jnp)

    def tile_copies(do, b, hi, first, end, t, slot):
        """Start (or wait for) the copies of tile ``t`` of the live entries
        ``first .. end`` of sequence ``b`` into buffer ``slot``: each
        entry's page of the step's KV heads, K and V, one DMA each straight
        to its place in the joined tile."""
        entry = first + t * pages

        def one(p, carry):
            page = tables_ref[b * mb + entry + p]
            at = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for kv, (pool, buf) in enumerate(((k_pool, k_buf),
                                              (v_pool, v_buf))):
                h0 = heads0_ref[kv] + hi * heads
                src = pool.at[pl.ds(h0, heads), page] if many \
                    else pool.at[h0, page]
                dst = buf.at[slot, :, at] if many else buf.at[slot, at]
                do(pltpu.make_async_copy(src, dst, sems.at[kv, slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(end - entry, pages), one, 0)

    def start_next(t, slot):
        """The tile after tile ``t`` of this step goes into ``slot`` while
        ``t`` is multiplied: this row block's next, or after its last the
        first tile of the grid's next step."""
        ni = i + 1
        roll = ni == pl.num_programs(2)
        ni = jnp.where(roll, 0, ni)
        nh = hi + roll.astype(jnp.int32)
        roll = nh == pl.num_programs(1)
        nh = jnp.where(roll, 0, nh)
        nb = b + roll.astype(jnp.int32)
        done = nb == pl.num_programs(0)
        nfirst, nend = span(jnp.where(done, b, nb), ni)
        own = t + 1 < n

        def pick(mine, following):
            return jnp.where(own, mine, following)

        @pl.when(jnp.logical_or(own, jnp.logical_not(done)))
        def _():
            tile_copies(lambda c: c.start(), pick(b, nb), pick(hi, nh),
                        pick(first, nfirst), pick(end, nend),
                        pick(t + 1, 0), slot)

    first, end = span(b, i)
    n = pl.cdiv(end - first, pages)

    @pl.when(jnp.logical_and(jnp.logical_and(b == 0, hi == 0), i == 0))
    def _first_step():
        # nobody fetched this step's first tile. A page that is not copied
        # (past a context, in a last tile) leaves its rows of the buffer as
        # they were: its keys are masked, and its values must be numbers
        slot_scr[0] = 0
        v_buf[:] = jnp.zeros_like(v_buf)
        tile_copies(lambda c: c.start(), b, hi, first, end, 0, 0)

    if sunk:
        # the sink is in the denominator before any key: exp(b - b) = 1
        m_scr[:] = sink_ref[head]
        l_scr[:] = jnp.ones_like(l_scr)
    else:
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    start = start_ref[b]

    def scaled(x, scale_ref, entry, dtype):
        # fp8 pages dequantize on load, entry by entry and head by head:
        # each page's scale rides in SMEM next to the block table
        tile = pages * bs
        where = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) // bs
        cols = []
        for h in range(heads):
            col = jnp.zeros((tile, 1), jnp.float32)
            for p in range(pages):
                page = tables_ref[b * mb + jnp.minimum(entry + p, mb - 1)]
                col = jnp.where(where == p, scale_ref[
                    (hi * heads + h) * num_blocks + page], col)
            cols.append(col)
        col = jnp.stack(cols) if many else cols[0]
        return (x.astype(jnp.float32) * col).astype(dtype)

    def fold(t, slot):
        start_next(t, 1 - slot)
        tile_copies(lambda c: c.wait(), b, hi, first, end, t, slot)
        entry = first + t * pages
        q = q_ref[0, head]                           # [(heads,) rows, d]
        k = k_buf[slot]                              # [(heads,) P * bs, d]
        v = v_buf[slot]
        if num_blocks:
            k = scaled(k, kscale_ref, entry, q.dtype)
            v = scaled(v, vscale_ref, entry, q.dtype)
        s = jax.lax.dot_general(q, k, (((q.ndim - 1,), (k.ndim - 1,)), batch),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap:                        # gemma2 attn_logit_softcapping
            s = softcap * jnp.tanh(s / softcap)
        # row r of the fold is (q-head r // chunk, chunk token r % chunk)
        row = i * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                  s.ndim - 2)
        qpos = start + row % chunk
        kpos = entry * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape,
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
        return 1 - slot

    slot_scr[0] = jax.lax.fori_loop(0, n, fold, slot_scr[0])
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
    doubles as the context-length mask, so stale tail entries of the last
    page are never visible, and the table entries past a row's last position
    are never read (whatever they name).
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
    dimensions merged, and the kernel's copies start at the layer's K and V
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
    """The kernel call: step ``hi`` of the grid's head axis copies pages of
    the ``heads`` rows of ``k_pages`` ([X, NB, bs, dk]) from ``heads0[0] + hi
    * heads`` and of ``v_pages`` ([Y, NB, bs, dv]) from ``heads0[1] + hi *
    heads`` (``heads`` is ``_tile``'s: ``hkv`` or 1); both pools stay in HBM
    whole. A function of its own under ``jit`` so that a step program traces
    and lowers it once a layer kind and not once a layer."""
    b, t, h, d = q.shape
    _, nb, bs, _ = k_pages.shape
    dv = v_pages.shape[-1]
    rep = h // hkv
    g = rep * t
    mb = block_tables.shape[1]
    itemsize = k_pages.dtype.itemsize
    rows, pages, heads = _tile(g, mb, bs, d, itemsize, hkv, dv, window)
    gp = -(-g // rows) * rows
    scaled = k_scales is not None
    sunk = sinks is not None

    qf = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, d)
    if gp != g:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    def rows_spec(width):
        return pl.BlockSpec((1, heads, rows, width),
                            lambda bi, hi, i, *pf: (bi, hi, i, 0))

    lead = (heads,) if heads > 1 else ()
    extra, extra_specs = [], []
    if sunk:
        # row r of a KV head's fold is query head r // t of its group: the
        # sink of every row, [Hkv, gp, 1] (0 on the fold's padding rows)
        per_row = jnp.repeat(sinks.astype(jnp.float32).reshape(hkv, rep), t,
                             axis=1)
        extra = [jnp.pad(per_row, ((0, 0), (0, gp - g)))[..., None]]
        extra_specs = [pl.BlockSpec((heads, rows, 1),
                                    lambda bi, hi, i, *pf: (hi, i, 0))]
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if scaled else 3,
        grid=(b, hkv // heads, gp // rows),
        in_specs=[rows_spec(d), pool_spec, pool_spec] + extra_specs,
        out_specs=rows_spec(dv),
        scratch_shapes=[
            pltpu.VMEM(lead + (rows, 1), jnp.float32),
            pltpu.VMEM(lead + (rows, 1), jnp.float32),
            pltpu.VMEM(lead + (rows, dv), jnp.float32),
            # the joined key and value tiles, two buffers deep
            pltpu.VMEM((2,) + lead + (pages * bs, d), k_pages.dtype),
            pltpu.VMEM((2,) + lead + (pages * bs, dv), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),       # the buffer of the next tile
        ],
    )
    prefetch = [block_tables.astype(jnp.int32).reshape(-1),
                start_pos.astype(jnp.int32), heads0.astype(jnp.int32)]
    if scaled:
        prefetch += [k_scales.reshape(-1).astype(jnp.float32),
                     v_scales.reshape(-1).astype(jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, pages=pages,
                          table_blocks=mb, chunk=t, rows=rows, heads=heads,
                          window=window, softcap=softcap, scale=scale,
                          sunk=sunk, num_blocks=nb if scaled else 0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, dv), q.dtype),
        # a step starts the copies of the step after it: in order, on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_vmem_limit(rows, pages, heads, bs, d, dv,
                                         itemsize)),
        interpret=interpret,
        name="paged_attention",
    )(*prefetch, qf, k_pages, v_pages, *extra)

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
