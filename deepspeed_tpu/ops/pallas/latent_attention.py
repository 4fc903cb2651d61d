"""Pallas attention over a latent (MLA) cache: a paged decode kernel over the
cached rows themselves, and a prefill kernel over keys and values up-projected
from them.

Reference analog: ``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``
has no latent form; the mechanism is DeepSeek-V2's (arXiv:2405.04434,
section 2.1).

A page holds ``block_size`` rows of ``[ckv (rank) ; k_rope]`` and has no
heads: every query head reads the same row. The pool's rows are padded with
zero lanes to a multiple of 128 (``row_width``): a last axis that is no
multiple of the TPU's lanes gets a device layout with the block index minor,
and a kernel that needs the row minor then has the whole pool copied in and
out of every step; the tiled row-major layout pads the row in memory anyway.

**Decode** (``latent_paged_attention``): the key up-projection is folded into
the query (``q~ = W_uk^T q_nope``) and the value up-projection into the
output, so a head scores against the whole row and sums its first ``rank``
values: one tile read serves both matmuls and all heads. **A grid step is one
sequence, and the kernel walks that sequence's own live pages with copies it
starts itself**, as ``paged_attention._paged_kernel`` does: the pool goes to
the call once, whole, as it lies in HBM (``memory_space=ANY``), block tables
and positions ride in scalar prefetch, and inside a step a loop runs over the
row's live tiles, ``ceil((pos // block_size + 1) / pages)`` of them: one DMA a
live table entry straight into its place in a joined ``[pages * block_size,
W]`` buffer, two buffers deep. The next tile's copies (after a row's last
tile, the NEXT row's first tile) start before this tile's products and are
waited for after them, across grid steps, which is why the grid's axis is
``arbitrary``: in order, on one core. No step exists for a tile past the
context; a last tile copies its live entries only (the buffer's other rows
keep finite rows of an earlier tile, masked by position: the buffers are
zeroed once, since a cached row is key AND value); a padding row of a batch
(position 0, trash table) costs one page. Only a row's last tile builds and
applies the mask (``kpos <= qpos``); the tiles below it are seen whole and
run ``_softmax_update`` with ``mask=None``. The running maximum and sum are
held lane-dense, as the prefill kernel's are. ``decode_tile_keys`` and
``decode_page_copies`` count, on plain ints, what a call multiplies and
copies.

Until PR 53 the grid was (sequence, page group) over the table BUCKET, a
tile's pages were ``pages`` pipelined operands joined with a ``concatenate``
every step, every step built the mask and the statistics were ``[rows, 1]``
columns: 64 rows over a 65-block bucket paid 320 grid steps whatever the
contexts held. Swept on a v5e (PR 53; one layer's call, ms; 32 folded heads
of 640 lanes, bfloat16, blocks of 64, contexts drawn as the cell's traffic
draws them; "bytes" is the contexts' rows at 819 GB/s):

- 64 rows over 65 blocks, contexts of 1,584 tokens in the mean (bytes 0.143):
  parent 0.519; own copies at 4 / 8 / 16 / 32 pages a tile 0.298 / 0.231 /
  **0.219** / 0.218;
- 32 rows over 132 blocks, 5,820 tokens (bytes 0.262): parent 0.524; 0.507 /
  0.375 / **0.340** / 0.344;
- 8 rows over 16 blocks, 600 tokens: parent 0.031; 0.029 / 0.028 / 0.031 /
  0.028; 4 rows over 32 blocks, 1,523 tokens: parent 0.029; 0.029 / 0.029 /
  0.028 / 0.028 (a call this small is its launch: no width is told from
  another).

So a tile is ``decode_pages``: ``_KEYS_PER_STEP`` = 1,024 keys (16 pages of
64), the table where it is shorter; static shapes alone choose. At 16 pages
the call moves its live pages at 72% of the published bandwidth (what a plain
elementwise pass reaches on this chip), so what is left is the bytes. Of the
ingredients, at 16 pages (64 x 65 / 32 x 132): ``[rows, 1]`` statistics 0.225
/ 0.344 (lane-dense is worth 2.8% / 1.0%); the mask built on every tile 0.219
/ 0.340 (two bodies are worth nothing here: the step is bound by its copies;
they stay because a row's last tile is a piece of code of its own anyway: it
waits for a part tile and starts the next row); the walk over live tiles
with the kernel's own copies is the rest (2.3x / 1.5x). Timed and dropped:
one wait a whole tile for all its copies' bytes (0.219 / 0.340) and the copy
loop unrolled (the same).

**Prefill** (``latent_prefill_attention``): a chunk's queries against
per-head keys ``[k_nope_i ; k_rope]`` and values of head dims 192 and 128,
2 x (192 + 128) operations a pair a head where the folded form costs
2 x (576 + 512). A flash forward over grid (head, query block, key block)
with the chunk's start in scalar prefetch; the rope part of a score is a
second small matmul against the one rotated key all heads share, so nothing
is concatenated or broadcast. Key blocks past a query block's causal horizon
are skipped, and map to the last live block so that they are not fetched.

What a grid step of it costs was swept on a v5e at the two latent cells'
shapes (PERF.md section 6, PR 46, has the table), and three things follow
from that sweep:

- the running maximum and sum are held lane-dense, ``[block_q, 128]`` with
  every lane the row's value. As ``[block_q, 1]`` columns they were broadcast
  across lanes in every step, and that, not the passes over the float32
  panel, was a third of a step: 2.2 -> 1.5 us a 512 x 512 panel;
- a panel is ``prefill_panel(t, s)``: 1,024 rows x 512 keys, or the call's
  own where it has fewer. Twice the rows pay the step's fixed cost and the
  key and value fetches once for twice the pairs (another 2-4%, 10% where
  half the grid is dead steps); wider key panels (1,024, 2,048) gained
  nothing further once the columns were lane-dense, and pad a table of 132
  blocks by up to 1,792 dead rows that are gathered and up-projected for
  nothing;
- the step has two bodies. A panel whose last key the row block's FIRST row
  already sees lies wholly under the horizon and runs no ``iota``, compare
  or ``where``; a panel the horizon crosses runs the masked step. On the
  unmasked panel the values are the masked step's bit for bit. Worth 2-5%
  with lane-dense columns (nothing before: the mask's passes hid under the
  broadcasts). ``prefill_panels`` counts both kinds and the dead steps.

Timed and dropped: one 192-wide score contraction over concatenated
``[q_nope ; q_rope]`` and ``[k_nope ; k_rope]`` (concatenated in VMEM: within
1% either way; as one more array a call: 3-23% slower), the row sum as a
matmul against ones (13-18% slower) and a mask from one ``iota`` difference
(no change). The scale stays a multiply on the float32 scores.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: Keys a decode grid step multiplies against, at most.
_KEYS_PER_STEP = 1024
#: Lanes of a vector register: the prefill kernel holds its running maximum
#: and sum this wide (every lane the row's value), as jax's own
#: ``pallas/ops/tpu/flash_attention.py`` does, where a ``[rows, 1]`` column
#: is broadcast across lanes in every step.
_LANES = 128
#: Query rows and keys of one prefill panel (a grid step's), at most.
_PANEL_ROWS = 1024
_PANEL_KEYS = 512


def _across(col, width: int):
    """A per-row value ``[rows, 1]``, or one held lane-dense ``[rows, 128]``
    (every lane the row's value), as wide as ``width`` lanes."""
    lanes = col.shape[1]
    if lanes in (1, width):
        return col
    if width % lanes:
        return col[:, :1]
    return pltpu.repeat(col, width // lanes, 1)


def _softmax_update(s, mask, v, m_prev, l_prev, acc_prev):
    """One flash step on scores ``s`` [rows, keys] against ``v``: the new
    ``(m, l, acc)``. ``m`` and ``l`` are ``[rows, 1]`` or lane-dense
    ``[rows, 128]``. ``mask`` None is a panel every row sees whole: no
    ``where``, and the same values bit for bit as an all-true mask gives
    (``where(True, x, ..)`` is ``x``)."""
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - _across(m_new, s.shape[1]))
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    return m_new, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), \
        acc_prev * _across(alpha, acc_prev.shape[1]) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _online_softmax_step(s, mask, v, m_scr, l_scr, acc_scr):
    m_scr[:], l_scr[:], acc_scr[:] = _softmax_update(
        s, mask, v, m_scr[:], l_scr[:], acc_scr[:])


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


# ---------------------------------------------------------------------------
# decode: folded queries over the cached rows
# ---------------------------------------------------------------------------
def decode_pages(mb: int, bs: int) -> int:
    """Pages of one key tile of the decode kernel over tables of ``mb``
    blocks of ``bs`` rows, from those two static sizes alone: up to
    ``_KEYS_PER_STEP`` keys, the table where it is shorter."""
    return max(min(_KEYS_PER_STEP // bs, mb), 1)


def _live_pages(pos, mb: int, bs: int, xp=np):
    """Table entries that hold a key the token at ``pos`` sees: up to its own
    page, or the table's end. ints or arrays with ``np``, traced scalars
    with ``jnp``."""
    return xp.minimum(pos // bs + 1, mb)


def decode_tile_keys(contexts, mb: int, bs: int) -> int:
    """Keys the decode kernel's tiles cover for a batch of ``contexts``
    tokens a sequence over tables of ``mb`` blocks: each context's live
    pages rounded out to whole key tiles. ``contexts`` over it is the tiles'
    fill: what the kernel multiplies against what a token sees."""
    pages = decode_pages(mb, bs)
    return sum(-(-int(_live_pages(c - 1, mb, bs)) // pages) * pages * bs
               for c in contexts)


def decode_page_copies(contexts, bucket: int, mb: int, bs: int) -> int:
    """Page copies one layer's decode call issues for a batch of
    ``contexts`` padded to ``bucket`` rows: one a live table entry (a
    padding row reads the one page of position 0)."""
    return sum(int(_live_pages(c - 1, mb, bs)) for c in contexts) \
        + bucket - len(contexts)


def _decode_kernel(tables_ref, pos_ref, q_ref, pool, o_ref, m_scr, l_scr,
                   acc_scr, kv_buf, sems, slot_scr, *, block_size, pages,
                   table_blocks, rank, scale):
    bs, mb = block_size, table_blocks
    keys = pages * bs
    b = pl.program_id(0)

    def tile_copies(do, b, count, t, slot):
        """Start (or wait for) the copies of the first ``count`` pages of
        tile ``t`` of sequence ``b`` into buffer ``slot``: one DMA a table
        entry straight to its place in the joined tile."""
        def one(p, carry):
            page = tables_ref[b * mb + t * pages + p]
            at = pl.ds(pl.multiple_of(p * bs, bs), bs)
            do(pltpu.make_async_copy(pool.at[page], kv_buf.at[slot, at],
                                     sems.at[slot]))
            return carry
        jax.lax.fori_loop(0, count, one, 0)

    end = _live_pages(pos_ref[b], mb, bs, jnp)
    n = pl.cdiv(end, pages)

    def live_in(end, t):
        """Pages of tile ``t`` that are among a row's live ``end``."""
        return jnp.minimum(end - t * pages, pages)

    @pl.when(b == 0)
    def _first_step():
        # nobody fetched this step's first tile. A page that is not copied
        # (past a context, in a last tile) leaves its rows of the buffer as
        # they were: as keys they are masked, and as values they must be
        # numbers
        slot_scr[0] = 0
        kv_buf[:] = jnp.zeros_like(kv_buf)
        tile_copies(lambda c: c.start(), b, live_in(end, 0), 0, 0)

    _init_scratch(m_scr, l_scr, acc_scr)

    def fold(slot, masked_at):
        q = q_ref[0]                                   # [rows, W]
        kv = kv_buf[slot].astype(q.dtype)              # [keys, W]
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = None
        if masked_at is not None:
            # causal == context-length mask; past the table nothing is live
            kpos = masked_at * keys + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos <= jnp.minimum(pos_ref[b], mb * bs - 1)
        _online_softmax_step(s, mask, kv[:, :rank], m_scr, l_scr, acc_scr)

    def whole(t, slot):
        # a tile below the one that holds the row's own position: every page
        # live, every key seen; the row's next tile is fetched meanwhile
        tile_copies(lambda c: c.start(), b, live_in(end, t + 1), t + 1,
                    1 - slot)
        tile_copies(lambda c: c.wait(), b, pages, t, slot)
        fold(slot, None)
        return 1 - slot

    slot = jax.lax.fori_loop(0, n - 1, whole, slot_scr[0])

    # the row's last tile, and the NEXT row's first meanwhile
    @pl.when(b + 1 < pl.num_programs(0))
    def _next_row():
        following = _live_pages(pos_ref[b + 1], mb, bs, jnp)
        tile_copies(lambda c: c.start(), b + 1, live_in(following, 0), 0,
                    1 - slot)

    tile_copies(lambda c: c.wait(), b, live_in(end, n - 1), n - 1, slot)
    fold(slot, n - 1)
    slot_scr[0] = 1 - slot
    l = _across(l_scr[:], acc_scr.shape[1])
    o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def latent_paged_attention(q, pool, layer: int, block_tables, positions,
                           scale: float, rank: int, interpret: bool = False):
    """q: [B, H, W] folded queries of one token a sequence, each
    ``[W_uk^T q_nope ; rope(q_rope) ; 0...]`` at the pool's row width; pool:
    the whole latent pool [L, NB, block_size, W] (it goes to the kernel as it
    lies in memory: a slice of it is a copy of it); block_tables: [B, MB]
    int32 (trash-padded); positions: [B] int32, each token's own position
    (it attends keys at positions <= its own). Returns [B, H, rank]: the
    probabilities times the first ``rank`` values of each row, before the
    value up-projection.

    The tokens' own rows must already be in the pages; causal masking then
    doubles as the context-length mask, and the table entries past a token's
    own page are never read (whatever they name)."""
    b, h, w = q.shape
    nb, bs = pool.shape[1], pool.shape[2]
    mb = block_tables.shape[1]
    pages = decode_pages(mb, bs)
    rows = -(-h // 8) * 8
    if rows != h:
        q = jnp.pad(q, ((0, 0), (0, rows - h), (0, 0)))
    flat = pool.reshape((-1,) + pool.shape[2:])        # [L * NB, bs, W]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, rows, w), lambda bi, *pf: (bi, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows, rank), lambda bi, *pf: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, rank), jnp.float32),
            # the joined tile of cached rows, two buffers deep
            pltpu.VMEM((2, pages * bs, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),       # the buffer of the next tile
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bs, pages=pages,
                          table_blocks=mb, rank=rank, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q.dtype),
        # a step starts the copies of the step after it: in order, on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_paged_attention",
    )((block_tables + layer * nb).reshape(-1).astype(jnp.int32),
      positions.astype(jnp.int32), q, flat)
    return out[:, :h]


def latent_paged_attention_reference(q, pages, block_tables, positions,
                                     scale: float, rank: int):
    """Gather-based jnp path with the decode kernel's semantics (the CPU's
    ``attn_impl`` and the kernel tests' oracle). pages: one layer's
    [NB, block_size, W]."""
    b = q.shape[0]
    mb, bs = block_tables.shape[1], pages.shape[1]
    ctx = pages[block_tables].reshape(b, mb * bs, -1).astype(q.dtype)
    s = jnp.einsum("bhd,bkd->bhk", q, ctx,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(mb * bs)[None, :] <= positions[:, None]    # [B, S]
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(ctx.dtype)
    return jnp.einsum("bhk,bkv->bhv", p, ctx[..., :rank])


# ---------------------------------------------------------------------------
# prefill: a chunk's queries over up-projected keys and values
# ---------------------------------------------------------------------------
def prefill_panel(t: int, s: int):
    """``(block_q, block_k)``: the query rows and the keys of one grid step
    for a chunk of ``t`` rows over ``s`` keys, from those two static sizes
    alone. The kernel cuts its grid by it and ``kv_cache._latent_prefill_attn``
    gathers ``prefill_keys`` of them, over which it is the same panel."""
    return min(t, _PANEL_ROWS), min(s, _PANEL_KEYS)


def prefill_keys(t: int, s: int) -> int:
    """``s`` keys rounded up to whole key panels of the call for ``t`` rows:
    what a block table is padded to before its rows are gathered."""
    block_k = prefill_panel(t, s)[1]
    return -(-s // block_k) * block_k


def prefill_panels(start: int, t: int, s: int) -> dict:
    """What one head's grid of one layer's call is made of, for a chunk of
    ``t`` rows at positions ``start ..`` over a table of ``s`` keys (gathered
    as ``prefill_keys`` of them): ``latent_panels`` that compute (some row
    sees some key), ``latent_panels_masked`` of them that the causal horizon
    crosses (they build and apply the mask; the others lie wholly under it)
    and ``latent_panels_dead`` grid steps past a row block's horizon, which
    fetch and compute nothing. The kernel's own guards, on plain ints."""
    block_q, block_k = prefill_panel(t, s)
    steps = prefill_keys(t, s) // block_k
    live = whole = 0
    for first in range(start, start + t, block_q):
        live += min((first + block_q - 1) // block_k + 1, steps)
        whole += min((first + 1) // block_k, steps)
    return {"latent_panels": live, "latent_panels_masked": live - whole,
            "latent_panels_dead": -(-t // block_q) * steps - live}


def _prefill_kernel(start_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                    m_scr, l_scr, acc_scr, *, block_q, block_k, steps, scale):
    i = pl.program_id(1)
    j = pl.program_id(2)

    pl.when(j == 0)(lambda: _init_scratch(m_scr, l_scr, acc_scr))
    first = start_ref[0] + i * block_q      # the row block's first position

    def _step(masked):
        contract = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(qn_ref[0], kn_ref[0], contract,
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr_ref[0], kr_ref[...], contract,
                                    preferred_element_type=jnp.float32)
        s = s * scale
        mask = None
        if masked:
            qpos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = j * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos <= qpos
        _online_softmax_step(s, mask, v_ref[0], m_scr, l_scr, acc_scr)

    # a panel's kind, once: the block's last row sees keys up to its own
    # position (live), and its FIRST row already sees a panel's last key
    # where the panel lies wholly under the horizon
    live = j * block_k <= first + block_q - 1
    whole = (j + 1) * block_k - 1 <= first
    pl.when(whole)(lambda: _step(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(whole)))(
        lambda: _step(True))

    @pl.when(j == steps - 1)
    def _finalize():
        l = _across(l_scr[:], acc_scr.shape[1])
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def latent_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, start,
                             scale: float, interpret: bool = False):
    """One sequence's chunk. q_nope: [H, T, d_n], q_rope: [H, T, d_r] (rows
    t at positions ``start + t``); k_nope: [H, S, d_n], k_rope: [S, d_r] (the
    one rotated key all heads share), v: [H, S, d_v], rows s at positions s,
    the chunk's own among them; start: int32 scalar. Row t attends keys at
    positions <= start + t. Returns [H, T, d_v]."""
    h, t, d_n = q_nope.shape
    s, d_r, d_v = k_nope.shape[1], q_rope.shape[2], v.shape[2]
    block_q, block_k = prefill_panel(t, s)
    tp = -(-t // block_q) * block_q
    if tp != t:
        q_nope = jnp.pad(q_nope, ((0, 0), (0, tp - t), (0, 0)))
        q_rope = jnp.pad(q_rope, ((0, 0), (0, tp - t), (0, 0)))
    if s % block_k:
        raise ValueError(f"{s} keys are no multiple of the key block "
                         f"{block_k}: gather a table padded to whole blocks")
    steps = s // block_k

    def key_block(i, j, start):
        # past the query block's horizon: the last live block again, which
        # the pipeline does not fetch twice
        return jnp.minimum(j, (start[0] + (i + 1) * block_q - 1) // block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, tp // block_q, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d_n), lambda hi, i, j, st: (hi, i, 0)),
            pl.BlockSpec((1, block_q, d_r), lambda hi, i, j, st: (hi, i, 0)),
            pl.BlockSpec((1, block_k, d_n), lambda hi, i, j, st:
                         (hi, key_block(i, j, st), 0)),
            pl.BlockSpec((block_k, d_r), lambda hi, i, j, st:
                         (key_block(i, j, st), 0)),
            pl.BlockSpec((1, block_k, d_v), lambda hi, i, j, st:
                         (hi, key_block(i, j, st), 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_v), lambda hi, i, j, st:
                               (hi, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, block_q=block_q, block_k=block_k,
                          steps=steps, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, tp, d_v), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="latent_prefill_attention",
    )(jnp.asarray(start, jnp.int32).reshape(1), q_nope, q_rope, k_nope,
      k_rope, v)
    return out[:, :t]


def latent_prefill_attention_reference(q_nope, q_rope, k_nope, k_rope, v,
                                       start, scale: float):
    """jnp path with the prefill kernel's semantics (the CPU's ``attn_impl``
    and the kernel tests' oracle)."""
    t, s = q_nope.shape[1], k_nope.shape[1]
    scores = (jnp.einsum("htd,hsd->hts", q_nope, k_nope,
                         preferred_element_type=jnp.float32) +
              jnp.einsum("htd,sd->hts", q_rope, k_rope,
                         preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(s)[None, :] <= start + jnp.arange(t)[:, None]
    scores = jnp.where(mask[None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("hts,hsv->htv", p, v)
